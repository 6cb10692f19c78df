"""Block-paged cache pool — one allocation per serving replica.

The pool is the serving engine's only cache memory.  It allocates what
the MODEL says it caches (`model.cache_planes()`): per layer, named
planes with their trailing shape per token -- `k` and `v` of
[Hkv, D] for the K/V models, ONE latent row for a latent-attention
model -- each a [num_blocks, block_size, *trailing] array allocated
ONCE and carved into fixed-size token blocks handed to requests through
a host-side free list with reference counts.  A block id means the same
block in every plane of every layer, so the allocator, the tables, the
refcounts and the scheduler never see the layout.  Freed requests return
their blocks immediately (refcount 0 -> back on the free list), so pool
pressure is a pure function of live context tokens — the scheduler
admits, evicts and preempts against `free_blocks`.

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

import jax.numpy as jnp

from ..distributed import mesh as mesh_mod
from ..resilience import chaos


class PoolExhausted(RuntimeError):
    """A single request needs more blocks than the whole pool holds."""


class BlockPool:
    def __init__(self, num_layers, num_blocks, block_size, planes,
                 dtype="float32", slots=0):
        """`planes`: {name: trailing shape per token}, the same for every
        layer (a K/V model's: `text.decode.kv_cache_planes`), or one such
        dict per layer, in which a `StatePlane` names a plane held per
        request: those get `slots` entries each."""
        from ..text.decode import StatePlane
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype
        per_layer = [planes] * self.num_layers if isinstance(planes, dict) \
            else list(planes)
        stateful = any(isinstance(spec, StatePlane)
                       for layer in per_layer for spec in layer.values())
        self.slots = int(slots) if stateful else 0
        if stateful and self.slots < 1:
            raise ValueError("layers that cache per request need slots")

        def plane(spec):
            if spec is None:
                return None
            if isinstance(spec, StatePlane):
                return jnp.zeros((self.slots,) + tuple(spec.shape),
                                 dtype=spec.dtype or dtype)
            return jnp.zeros((self.num_blocks, self.block_size)
                             + tuple(int(n) for n in spec), dtype=dtype)

        names = list(dict.fromkeys(n for layer in per_layer for n in layer))
        # {name: one array a layer, None where the layer has no such plane}
        self.planes = {name: [plane(layer.get(name)) for layer in per_layer]
                       for name in names}
        self.state_names = frozenset(
            n for layer in per_layer for n, spec in layer.items()
            if isinstance(spec, StatePlane))
        self._state_bytes = sum(
            a.nbytes // self.slots for name in self.state_names
            for a in self.planes[name] if a is not None)
        # host-side allocators: LIFO free lists; per-block refcounts
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._refs = [0] * self.num_blocks
        self._free_slots = list(range(self.slots - 1, -1, -1))

    @classmethod
    def for_model(cls, model, num_blocks, block_size=16, dtype=None,
                  slots=0):
        """Size the pool from what the model declares it caches
        (`cache_planes()`: one {name: trailing shape | StatePlane} per
        layer); `slots` entries of every per-request plane."""
        per_layer = model.cache_planes()
        if dtype is None:
            dtype = next(iter(model.parameters()))._array.dtype
        return cls(len(per_layer), num_blocks, block_size, per_layer,
                   dtype=dtype, slots=slots)

    def plane_shapes(self):
        """{name: one layer's array shape} of the planes held per token."""
        return {name: next(a for a in arrays if a is not None).shape
                for name, arrays in self.planes.items()
                if name not in self.state_names}

    def state_bytes(self):
        """Bytes one request's slot holds over all layers."""
        return self._state_bytes

    def release(self):
        """Drop the device arrays (the allocator's books stay)."""
        self.planes = {}

    # ------------------------------------------------------------ allocator
    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.num_blocks - len(self._free)

    def blocks_for(self, n_tokens):
        """Blocks needed to hold n_tokens."""
        return -(-int(n_tokens) // self.block_size)

    def allocate(self, n):
        """n block ids at refcount 1, or None when the pool can't serve
        them right now (the scheduler's preemption trigger).  The
        `serving.pool_exhausted` chaos site simulates that exhaustion."""
        n = int(n)
        if n > self.num_blocks:
            raise PoolExhausted(
                f"request needs {n} blocks but the whole pool is only "
                f"{self.num_blocks}; grow num_blocks or cap request "
                f"lengths")
        if chaos.fire("serving.pool_exhausted") or n > len(self._free):
            from ..observability import metrics as _metrics
            _metrics.registry().counter(
                "serving_pool_exhausted_total").inc()
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, ids):
        for b in ids:
            if self._refs[b] <= 0:
                raise ValueError(f"ref of unallocated block {b}")
            self._refs[b] += 1

    def free(self, ids):
        """Drop one reference per id; blocks at refcount 0 return to the
        free list immediately."""
        for b in ids:
            r = self._refs[b] - 1
            if r < 0:
                raise ValueError(f"double free of block {b}")
            self._refs[b] = r
            if r == 0:
                self._free.append(b)

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
        and every slot is home (a slot still out shows among the leaked
        as ``("slot", n)``).  The chaos drill asserts this after an
        overload run."""
        leaked = [b for b, r in enumerate(self._refs) if r > 0]
        leaked += [("slot", n) for n in range(self.slots)
                   if n not in self._free_slots]
        bad = [b for b, r in enumerate(self._refs) if r < 0]
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
        for name, shape in self.plane_shapes().items():
            if len(shape) == 4 and shape[2] % mesh_mod.degree("mp") == 0:
                self.planes[name] = [a if a is None else jax.device_put(a, sh)
                                     for a in self.planes[name]]
                done = True
        return done
