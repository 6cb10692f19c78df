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
                 dtype="float32"):
        """`planes`: {name: trailing shape per token}, the same for every
        layer (a K/V model's: `text.decode.kv_cache_planes`)."""
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype
        self.planes = {
            name: [jnp.zeros((self.num_blocks, self.block_size)
                             + tuple(int(n) for n in trailing), dtype=dtype)
                   for _ in range(self.num_layers)]
            for name, trailing in planes.items()}
        # host-side allocator: LIFO free list + per-block refcounts
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._refs = [0] * self.num_blocks

    @classmethod
    def for_model(cls, model, num_blocks, block_size=16, dtype=None):
        """Size the pool from what the model declares it caches
        (`cache_planes()`: one {name: trailing shape} per layer)."""
        per_layer = model.cache_planes()
        if any(p != per_layer[0] for p in per_layer):
            raise NotImplementedError(
                "layers that cache different planes need a pool per kind")
        if dtype is None:
            dtype = next(iter(model.parameters()))._array.dtype
        return cls(len(per_layer), num_blocks, block_size, per_layer[0],
                   dtype=dtype)

    def plane_shapes(self):
        """{name: one layer's array shape}."""
        return {name: arrays[0].shape
                for name, arrays in self.planes.items()}

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

    def check_leaks(self):
        """(leaked_blocks, bad_refcounts) — both empty when every block
        is home.  The chaos drill asserts this after an overload run."""
        leaked = [b for b, r in enumerate(self._refs) if r > 0]
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
        for name, arrays in self.planes.items():
            if arrays[0].ndim == 4 \
                    and arrays[0].shape[2] % mesh_mod.degree("mp") == 0:
                self.planes[name] = [jax.device_put(a, sh) for a in arrays]
                done = True
        return done
