"""Pallas TPU kernel overrides — the fused-GPU-kernel registry analog.

Reference: paddle registers hand-fused CUDA kernels (flash_attn,
fused_softmax_mask, ...) into PHI at build time; here pallas kernels
override registry entries at import.  The override decides per call
whether the pallas path applies (backend, shapes, mask) and otherwise
falls through to the XLA implementation, so numerics are always defined.

Env control: PADDLE_TPU_PALLAS=0 disables, =interpret forces the pallas
kernels in interpreter mode (CPU tests).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec as P

from ..dispatch import get, override
from . import flash_attention as _fa
from . import paged_attention as _pa


def _mode():
    env = os.environ.get("PADDLE_TPU_PALLAS", "").lower()
    if env in ("0", "off", "false"):
        return None
    if env == "interpret":
        return "interpret"
    return "tpu" if jax.devices()[0].platform == "tpu" else None


def _mesh_split():
    """How a kernel call splits over the fleet mesh.  Mosaic kernels
    cannot be partitioned by GSPMD, so under a multi-device mesh the
    kernel runs inside `jax.shard_map`: batch on "dp", heads on "mp".

    Returns None without a multi-device mesh, else (mesh, auto, names):
    `auto` is the set of mesh axes not manual yet at this point of the
    trace (a pipeline stage body already runs pp-manual; a caller's own
    shard_map may hold dp/mp) — the shard_map takes ALL of them, since
    the compiler refuses the kernel while any axis is automatic, even
    one of degree 1; `names` maps "dp"/"mp" to the axis name to shard
    over, or to None where that axis has degree 1 or is manual already.
    `names` is None when some OTHER axis of degree > 1 is still
    automatic (e.g. "ep"): no kernel then, XLA path."""
    from ...distributed import mesh as mesh_mod   # ops load first
    if not mesh_mod.has_mesh():
        return None
    mesh = mesh_mod.get_mesh()
    if mesh.size == 1:
        return None
    ctx = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(ctx.manual_axes)
    names = {a: a if a in auto and mesh.shape[a] > 1 else None
             for a in ("dp", "mp")}
    if any(mesh.shape[a] > 1 for a in auto - {"dp", "mp"}):
        names = None
    # nested in a manual region, shard_map wants the context's own mesh
    return (ctx if ctx.manual_axes else mesh), auto, names


def _shards(split):
    """(dp, mp) shard counts of a `_mesh_split()` result for supports();
    None = the mesh forbids the kernel."""
    if split is None:
        return 1, 1
    mesh, _, names = split
    if names is None:
        return None
    return tuple(mesh.shape[n] if n else 1
                 for n in (names["dp"], names["mp"]))


def _over_mesh(split, kernel, args, specs, out_spec):
    """Run `kernel(*args)` per shard of the fleet mesh (or directly
    when no axis is left automatic).  `specs` use "dp"/"mp" as
    placeholders; they resolve through the split's axis names."""
    if split is None or not split[1]:
        return kernel(*args)
    mesh, auto, names = split

    def resolve(spec):
        return P(*(names.get(a) if a else None for a in spec))

    return jax.shard_map(
        kernel, mesh=mesh, in_specs=tuple(resolve(s) for s in specs),
        out_specs=resolve(out_spec), axis_names=auto,
        check_vma=False)(*args)


_BLHD = ("dp", None, "mp", None)       # batch on dp, heads on mp

_xla_sdpa = get("sdpa").fn


def _mask_spec(mask, q_shape):
    """Mask [mb, mh, mlq, Lk] follows q where it is not broadcast."""
    return ("dp" if mask.shape[0] == q_shape[0] and q_shape[0] > 1 else None,
            "mp" if mask.shape[1] == q_shape[2] and q_shape[2] > 1 else None,
            None, None)


def sdpa_with_flash(q, k, v, mask=None, is_causal=False, scale=None,
                    sliding_window=None, _mask_needs_grad=False):
    mode = _mode()
    split = _mesh_split() if mode is not None else None
    if mode is not None and not _mask_needs_grad and \
            (not sliding_window or is_causal) and \
            _fa.supports(q.shape, k.shape, mask, q.dtype,
                         v_shape=v.shape, is_causal=is_causal,
                         shards=_shards(split)):
        def kernel(q, k, v, mask=None):
            return _fa.flash_attention(
                q, k, v, mask=mask, is_causal=is_causal, scale=scale,
                window=sliding_window, interpret=(mode == "interpret"))

        args, specs = (q, k, v), (_BLHD,) * 3
        if mask is not None:
            args += (mask,)
            specs += (_mask_spec(mask, q.shape),)
        return _over_mesh(split, kernel, args, specs, _BLHD)
    return _xla_sdpa(q, k, v, mask=mask, is_causal=is_causal, scale=scale,
                     sliding_window=sliding_window)


override("sdpa", sdpa_with_flash)


_xla_paged_attention = get("paged_attention").fn


def _paged_kernel(q_shape, pool_shape, dtype):
    """(mode, split) where the pallas kernel serves a `paged_attention`
    call of these shapes, here and now (backend, PADDLE_TPU_PALLAS, the
    mesh); None where the XLA gather does."""
    mode = _mode()
    if mode is None:
        return None
    split = _mesh_split()
    shards = _shards(split)
    if shards is None or not _pa.supports(q_shape, pool_shape, dtype,
                                          mp=shards[1]):
        return None
    return mode, split


def paged_attention_with_pallas(q, k_pool, v_pool, tables, pos, scale=None):
    """Serving decode steps stream blocks through the pallas kernel;
    prefill chunks (s > 1) and unsupported shapes keep the XLA gather
    fallback, which is also the parity reference.  Under a mesh the
    kernel sees its replica's local heads: q and the pool shard on "mp"
    (`BlockPool.shard_`), tables and positions are replicated."""
    served = _paged_kernel(q.shape, k_pool.shape, q.dtype)
    if served is not None:
        mode, split = served

        def kernel(q, k_pool, v_pool, tables, pos):
            return _pa.paged_decode_attention(
                q, k_pool, v_pool, tables, pos + 1, scale=scale,
                interpret=(mode == "interpret"))

        heads = (None, None, "mp", None)
        return _over_mesh(split, kernel, (q, k_pool, v_pool, tables, pos),
                          (heads, heads, heads, (None, None), (None,)),
                          heads)
    return _xla_paged_attention(q, k_pool, v_pool, tables, pos, scale=scale)


def paged_blocks_read(lens, table_cols, q_shape, pool_shape, dtype):
    """Pool blocks one `paged_attention` call reads for rows of visible
    lengths `lens` (host numbers), by the path that serves those shapes:
    the kernel's ragged walk (`walked_blocks`), or every column of every
    row's table where the XLA fallback gathers.  The gate is the one the
    call itself takes; nothing is read back from the device."""
    if _paged_kernel(q_shape, pool_shape, dtype) is None:
        return len(lens) * table_cols
    return _pa.walked_blocks(lens, table_cols, pool_shape[1])


override("paged_attention", paged_attention_with_pallas)
