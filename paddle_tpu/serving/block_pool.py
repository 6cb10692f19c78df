"""Block-paged cache pool — one allocation per serving replica.

The pool is the serving engine's only cache memory.  It allocates what
the MODEL says it caches (`model.cache_planes()`): per layer, named
planes with their trailing shape per token -- `k` and `v` of
[Hkv, D] for the K/V models, ONE latent row for a latent-attention
model -- each a [num_blocks, block_size, *trailing] array allocated
ONCE and carved into fixed-size token blocks handed to requests through
a host-side free list with reference counts.  Freed requests return
their blocks immediately (refcount 0 -> back on the free list), so pool
pressure is a pure function of live context tokens — the scheduler
admits, evicts and preempts against `free_blocks`.  The free list is a
min-heap: `allocate` hands out the LOWEST free ids, so the blocks of one
allocation, and of allocations that follow one another, lie side by side
in the pool wherever the free ids do -- a walk that copies a run of
adjacent blocks in one DMA (the sparse indexer's) meets runs.

Blocks come in GROUPS, one per lifetime (`text.decode.LayerPlanes`): a
group is the layers whose blocks live equally long, and has its own
number of blocks, free list and refcounts; a request holds one table a
group, and a block id means the same block in every plane of every layer
OF ITS GROUP, so the allocator, the tables and the scheduler never see
the layout.  The layers that keep the whole context are one group (a
model of one kind is one group, and the pool is what it was).  Layers
that read their last `window` positions alone are another: its table is
still indexed by position (``table[p // block_size]``), but an entry goes
home as soon as it lies wholly behind the band (`Scheduler.trim`), so a
request holds blocks for its window and the chunk in flight, not for its
context; the entry behind the band may then hold any id, which the paged
ops never read.

Layers of different KINDS cache different planes, side by side in the
one pool: a plane exists for the layers that name it and is None for the
others (a model of one GQA layer in four holds K/V blocks for that layer
alone).  A layer may also cache per REQUEST instead of per token
(`text.decode.StatePlane`: a recurrent state, a convolution's tail): such
a plane is [slots, *shape], one entry a running request, handed out by a
slot allocator beside the block allocator.  A request then owns a block
table AND a slot; both go home when it finishes or is preempted.

Mesh layout: a plane with a kv-head axis ([N, bs, Hkv, D]) shards that
axis — `shard_()` places it as PartitionSpec(None, None, "mp", None) on
the fleet mesh, the same axis the model's ColumnParallel qkv projections
shard, so a tensor-parallel replica's pool shards with its weights and
the paged attention op runs on local heads only.  A plane without one
(a latent row, which every head reads) stays replicated.
"""
from __future__ import annotations

import heapq

import jax.numpy as jnp

from ..distributed import mesh as mesh_mod
from ..resilience import chaos


class PoolExhausted(RuntimeError):
    """A single request needs more blocks than the whole pool holds."""


def band_blocks(window, n_tokens, block_size):
    """The most blocks a request holds at a time in a group of `window`
    while one program writes `n_tokens`: the band behind the context
    (with its one position of slack: `Scheduler.trim`) and the run, which
    may start and end inside a block."""
    return -(-(int(window) + int(n_tokens) - 1) // int(block_size)) + 1


class BlockGroup:
    """The blocks of the layers of one lifetime: how many, which are
    free (a min-heap: the lowest id first), how often each is
    referenced."""

    def __init__(self, name, window, num_blocks):
        self.name, self.window = name, window
        self.num_blocks = int(num_blocks)
        self.layers = []        # the layers whose planes these blocks are
        self.free = list(range(self.num_blocks))    # ascending: a heap
        self.refs = [0] * self.num_blocks


class BlockPool:
    def __init__(self, num_layers, num_blocks, block_size, planes,
                 dtype="float32", slots=0, window_blocks=None):
        """`planes`: {name: trailing shape per token}, the same for every
        layer (a K/V model's: `text.decode.kv_cache_planes`), or one such
        dict per layer, in which a `StatePlane` names a plane held per
        request: those get `slots` entries each.  A layer's dict may be a
        `LayerPlanes` that names a `window`: the layers of one window are
        a group of `window_blocks` blocks ({window: blocks}; `num_blocks`
        where it names none), beside the `num_blocks` of the layers that
        keep the whole context."""
        from ..text.decode import StatePlane
        self.num_layers = int(num_layers)
        self.block_size = int(block_size)
        self.dtype = dtype
        per_layer = [planes] * self.num_layers if isinstance(planes, dict) \
            else list(planes)
        # groups by lifetime, the whole-context one first: `num_blocks`,
        # `free_blocks`, `allocate` ... without a group mean group 0
        windows = [getattr(layer, "window", None) for layer in per_layer]
        self.groups, self.group_of = [], []
        for w in sorted(set(windows), key=lambda w: (w is not None, w)):
            first = per_layer[windows.index(w)]
            n = num_blocks if w is None or not self.groups \
                else (window_blocks or {}).get(w, num_blocks)
            self.groups.append(BlockGroup(
                getattr(first, "kind", "full"), w, n))
        for i, w in enumerate(windows):
            g = next(k for k, grp in enumerate(self.groups)
                     if grp.window == w)
            self.groups[g].layers.append(i)
            self.group_of.append(g)
        stateful = any(isinstance(spec, StatePlane)
                       for layer in per_layer for spec in layer.values())
        self.slots = int(slots) if stateful else 0
        if stateful and self.slots < 1:
            raise ValueError("layers that cache per request need slots")

        def plane(spec):    # of `blocks` blocks, the layer's group's
            if spec is None:
                return None
            if isinstance(spec, StatePlane):
                return jnp.zeros((self.slots,) + tuple(spec.shape),
                                 dtype=spec.dtype or dtype)
            return jnp.zeros((blocks, self.block_size)
                             + tuple(int(n) for n in spec), dtype=dtype)

        names = list(dict.fromkeys(n for layer in per_layer for n in layer))
        # {name: one array a layer, None where the layer has no such plane}
        self.planes = {name: [] for name in names}
        for i, layer in enumerate(per_layer):
            blocks = self.groups[self.group_of[i]].num_blocks
            for name in names:
                self.planes[name].append(plane(layer.get(name)))
        self.state_names = frozenset(
            n for layer in per_layer for n, spec in layer.items()
            if isinstance(spec, StatePlane))
        self._state_bytes = sum(
            a.nbytes // self.slots for name in self.state_names
            for a in self.planes[name] if a is not None)
        self._free_slots = list(range(self.slots - 1, -1, -1))

    @classmethod
    def for_model(cls, model, num_blocks, block_size=16, dtype=None,
                  slots=0, window_blocks=None):
        """Size the pool from what the model declares it caches
        (`cache_planes()`: one {name: trailing shape | StatePlane} per
        layer); `slots` entries of every per-request plane."""
        per_layer = model.cache_planes()
        if dtype is None:
            dtype = next(iter(model.parameters()))._array.dtype
        return cls(len(per_layer), num_blocks, block_size, per_layer,
                   dtype=dtype, slots=slots, window_blocks=window_blocks)

    @property
    def num_blocks(self):
        return self.groups[0].num_blocks

    # the first group's books under the names they had before the
    # groups: read by two older test lines alone, to go with them at the
    # next simplicity pass
    _free = property(lambda self: self.groups[0].free)
    _refs = property(lambda self: self.groups[0].refs)

    def plane_shapes(self, group=0):
        """{name: one layer's array shape} of the planes the layers of
        `group` hold per token."""
        layers = self.groups[group].layers
        return {name: next(arrays[i] for i in layers
                           if arrays[i] is not None).shape
                for name, arrays in self.planes.items()
                if name not in self.state_names
                and any(arrays[i] is not None for i in layers)}

    def state_bytes(self):
        """Bytes one request's slot holds over all layers."""
        return self._state_bytes

    def release(self):
        """Drop the device arrays (the allocator's books stay)."""
        self.planes = {}

    # ------------------------------------------------------------ allocator
    # every call takes the group it means; without one, the first
    @property
    def free_blocks(self):
        return len(self.groups[0].free)

    @property
    def used_blocks(self):
        return self.num_blocks - self.free_blocks

    def used_in(self, group):
        grp = self.groups[group]
        return grp.num_blocks - len(grp.free)

    def blocks_for(self, n_tokens):
        """Blocks needed to hold n_tokens."""
        return -(-int(n_tokens) // self.block_size)

    def band_blocks(self, group, n_tokens):
        """`band_blocks` of `group`; None for the group that keeps the
        whole context."""
        window = self.groups[group].window
        return None if window is None \
            else band_blocks(window, n_tokens, self.block_size)

    def allocate(self, n, group=0):
        """The n lowest free block ids of `group`, in ascending order,
        at refcount 1, or None when it can't serve them right now (the
        scheduler's preemption trigger).  The `serving.pool_exhausted`
        chaos site simulates that exhaustion."""
        n, grp = int(n), self.groups[group]
        if n > grp.num_blocks:
            raise PoolExhausted(
                f"request needs {n} {grp.name} blocks but the whole pool "
                f"has only {grp.num_blocks} of that kind; grow num_blocks "
                f"or cap request lengths")
        if chaos.fire("serving.pool_exhausted") or n > len(grp.free):
            from ..observability import metrics as _metrics
            # the total its readers know, and the same by kind
            for labels in ({}, {"kind": grp.name}):
                _metrics.registry().counter(
                    "serving_pool_exhausted_total", **labels).inc()
            return None
        out = [heapq.heappop(grp.free) for _ in range(n)]
        for b in out:
            grp.refs[b] = 1
        return out

    def ref(self, ids, group=0):
        refs = self.groups[group].refs
        for b in ids:
            if refs[b] <= 0:
                raise ValueError(f"ref of unallocated block {b}")
            refs[b] += 1

    def free(self, ids, group=0):
        """Drop one reference per id; blocks at refcount 0 return to the
        free list immediately."""
        grp = self.groups[group]
        for b in ids:
            r = grp.refs[b] - 1
            if r < 0:
                raise ValueError(f"double free of {grp.name} block {b}")
            grp.refs[b] = r
            if r == 0:
                heapq.heappush(grp.free, b)

    # ------------------------------------------------------ slot allocator
    @property
    def free_slots(self):
        return len(self._free_slots)

    def allocate_slot(self):
        """A state slot, or None when every one is taken (or the pool
        has none)."""
        return self._free_slots.pop() if self._free_slots else None

    def free_slot(self, slot):
        if not 0 <= slot < self.slots or slot in self._free_slots:
            raise ValueError(f"free of unallocated slot {slot}")
        self._free_slots.append(slot)

    def check_leaks(self):
        """(leaked_blocks, bad_refcounts) — both empty when every block
        of every group and every slot is home (a block of a further group
        shows as ``(its kind, n)``, a slot still out as ``("slot", n)``).
        The chaos drill asserts this after an overload run."""
        leaked, bad = [], []
        for g, grp in enumerate(self.groups):
            tag = (lambda b: b) if g == 0 else (lambda b: (grp.name, b))
            leaked += [tag(b) for b, r in enumerate(grp.refs) if r > 0]
            bad += [tag(b) for b, r in enumerate(grp.refs) if r < 0]
        leaked += [("slot", n) for n in range(self.slots)
                   if n not in self._free_slots]
        return leaked, bad

    # ------------------------------------------------------------- sharding
    def shard_(self):
        """Lay the pool out on the fleet mesh: planes with a kv-head axis
        shard it along "mp" (the tensor-parallel axis the qkv projections
        shard), everything else is replicated.  No-op without a
        multi-device mp mesh or when heads don't divide it."""
        if not mesh_mod.has_mesh() or mesh_mod.degree("mp") <= 1:
            return False
        import jax
        sh = mesh_mod.sharding(None, None, "mp", None)
        done = False
        for name, arrays in self.planes.items():
            if name in self.state_names:
                continue
            shape = next(a for a in arrays if a is not None).shape
            if len(shape) == 4 and shape[2] % mesh_mod.degree("mp") == 0:
                self.planes[name] = [a if a is None else jax.device_put(a, sh)
                                     for a in arrays]
                done = True
        return done
