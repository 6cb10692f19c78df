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
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dispatch import get, override
from . import flash_attention as _fa
from . import kda as _kda
from . import latent_paged_attention as _la
from . import paged_attention as _pa
from . import sparse_attention as _sa


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


def paged_attention_with_pallas(q, k_pool, v_pool, tables, pos, scale=None,
                                window=None):
    """Serving programs walk a row's live blocks through a pallas
    kernel: a decode step (one query row a request) through the decode
    kernel, a prefill chunk (s > 1) through the prefill kernel; the
    query's shape chooses.  Unsupported shapes keep the XLA gather
    fallback, which is also the parity reference.  Under a mesh the
    kernel sees its replica's local heads: q and the pool shard on "mp"
    (`BlockPool.shard_`), tables and positions are replicated."""
    served = _paged_kernel(q.shape, k_pool.shape, q.dtype)
    if served is not None:
        mode, split = served

        def kernel(q, k_pool, v_pool, tables, pos):
            if q.shape[1] > 1:
                return _pa.paged_prefill_attention(
                    q, k_pool, v_pool, tables, pos, scale=scale,
                    interpret=(mode == "interpret"), window=window)
            return _pa.paged_decode_attention(
                q, k_pool, v_pool, tables, pos + 1, scale=scale,
                interpret=(mode == "interpret"), window=window)

        heads = (None, None, "mp", None)
        return _over_mesh(split, kernel, (q, k_pool, v_pool, tables, pos),
                          (heads, heads, heads, (None, None), (None,)),
                          heads)
    return _xla_paged_attention(q, k_pool, v_pool, tables, pos, scale=scale,
                                window=window)


def paged_blocks_read(lens, table_cols, q_shape, pool_shape, dtype,
                      window=None):
    """Pool blocks one `paged_attention` call reads for rows whose last
    query sees `lens` positions (host numbers; `q_shape[1]` queries a
    row), by the path that serves those shapes: a kernel's ragged walk
    (`walked_blocks`), or every column of every row's table where the
    XLA fallback gathers (under a `window`: the columns the row's
    queries can see).  The gate is the one the call itself takes;
    nothing is read back from the device."""
    queries = q_shape[1]
    if _paged_kernel(q_shape, pool_shape, dtype) is None:
        if window is not None:      # the columns the queries can see
            table_cols = min(table_cols, _pa.band_blocks(
                window + queries, pool_shape[1]))
        return len(lens) * table_cols
    return _pa.walked_blocks(lens, table_cols, pool_shape[1], window,
                             queries)


override("paged_attention", paged_attention_with_pallas)


_xla_latent_paged_attention = get("latent_paged_attention").fn


def _latent_kernel(q_shape, pool_shape, value_dim, dtype):
    """As `_paged_kernel`, for a `latent_paged_attention` call."""
    mode = _mode()
    if mode is None:
        return None
    split = _mesh_split()
    shards = _shards(split)
    if shards is None or not _la.supports(q_shape, pool_shape, value_dim,
                                          dtype, mp=shards[1]):
        return None
    return mode, split


def latent_paged_attention_with_pallas(q, pool, tables, pos, value_dim,
                                       scale=None):
    """Serving programs walk a row's live blocks of the latent pool
    through a pallas kernel: a decode step (one query row a request)
    through the decode kernel, a prefill chunk (s > 1) through the
    prefill kernel; the query's shape chooses.  Unsupported shapes keep
    the XLA gather, which is also the parity reference.  Under a mesh
    the queries' heads shard on "mp"; the pool's rows serve every head
    and are replicated, as are tables and positions."""
    served = _latent_kernel(q.shape, pool.shape, value_dim, q.dtype)
    if served is not None:
        mode, split = served

        def kernel(q, pool, tables, pos):
            if q.shape[1] > 1:
                return _la.latent_paged_prefill_attention(
                    q, pool, tables, pos, value_dim, scale=scale,
                    interpret=(mode == "interpret"))
            return _la.latent_paged_decode_attention(
                q, pool, tables, pos + 1, value_dim, scale=scale,
                interpret=(mode == "interpret"))

        heads = (None, None, "mp", None)
        return _over_mesh(split, kernel, (q, pool, tables, pos),
                          (heads, (None, None, None), (None, None),
                           (None,)), heads)
    return _xla_latent_paged_attention(q, pool, tables, pos, value_dim,
                                       scale=scale)


def latent_blocks_read(lens, table_cols, q_shape, pool_shape, dtype):
    """As `paged_blocks_read`, for a `latent_paged_attention` call: the
    latent kernels' walk (`walked_blocks`, the decode kernel's for one
    query a row, the prefill kernel's for more), or every column of
    every row's table where the XLA fallback gathers.  The values are
    the leading lanes of a row: for the gate, any whole lane tiles of
    it, so the row's own width stands in."""
    if _latent_kernel(q_shape, pool_shape, pool_shape[2], dtype) is None:
        return len(lens) * table_cols
    return _la.walked_blocks(lens, table_cols, pool_shape[1],
                             queries=q_shape[1])


override("latent_paged_attention", latent_paged_attention_with_pallas)


_xla_sparse_paged_attention = get("sparse_paged_attention").fn


def _sparse_kernel(q_shape, pool_shape, ik_shape, dtype):
    """The kernels' mode where they serve a `sparse_paged_attention` call
    of these shapes, here and now; None where the XLA form does (CPU,
    PADDLE_TPU_PALLAS=0, a fleet mesh, shapes the kernels do not take)."""
    mode = _mode()
    if mode is None or _mesh_split() is not None \
            or not _sa.supports(q_shape, pool_shape, ik_shape, dtype) \
            or not _pa.supports(q_shape, pool_shape, dtype):
        return None
    return mode


def sparse_paged_attention_with_pallas(q, k_pool, v_pool, ik_pool, q_idx,
                                       w_idx, tables, pos, topk, scale=None):
    """On TPU the indexer's kernel scores the positions a query sees
    (`sparse_attention.indexer_scores`) and the attention reads the
    picks: a decode step XLA's top `topk` positions' K and V rows alone
    (`sparse_attention.picked_attention`), a prefill chunk the prefill kernel's
    walk with each query's threshold, its `topk`-th largest score
    (`sparse_attention.topk_threshold`; `paged_prefill_attention(
    picks=)`).  Anything else keeps the XLA
    form, which is also the parity reference."""
    mode = _sparse_kernel(q.shape, k_pool.shape, ik_pool.shape, q.dtype)
    if mode is None:
        return _xla_sparse_paged_attention(q, k_pool, v_pool, ik_pool, q_idx,
                                           w_idx, tables, pos, topk,
                                           scale=scale)
    interpret = mode == "interpret"
    scores = _sa.indexer_scores(q_idx, w_idx, ik_pool, tables, pos,
                                interpret=interpret)
    bs, cols = k_pool.shape[1], tables.shape[1]
    B, s = q.shape[:2]
    if s > 1:
        # the walk's last chunk may pass the scored columns
        walk = _pa.chunk_blocks(cols, bs, k_pool.shape[2], k_pool.shape[3],
                                k_pool.dtype) * bs
        short = -(-cols * bs // walk) * walk - scores.shape[-1]
        if short > 0:
            scores = jnp.pad(scores, ((0, 0), (0, 0), (0, short)),
                             constant_values=-jnp.inf)
        L = scores.shape[-1]
        # a tile's last query sees its `tile` more positions than the last
        tile = _sa.THRESHOLD_ROWS
        seen = pos[:, None] + tile * jnp.arange(1, s // tile + 1)
        tau = _sa.topk_threshold(
            scores.reshape(B * s, L), jnp.minimum(seen, L).reshape(-1),
            min(int(topk), L), interpret=interpret).reshape(B, s, 1)
        return _pa.paged_prefill_attention(
            q, k_pool, v_pool, tables, pos, scale=scale, interpret=interpret,
            picks=(scores, tau))
    # a decode step's few rows: XLA's top-k (0.8 ms for 12 rows of
    # 51,200 on a v5e) beats the threshold and a list of the positions
    # over it (4.4 ms)
    k = min(int(topk), scores.shape[-1])
    picked = jax.lax.top_k(scores[:, 0], k)[1]
    rows = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.minimum(picked // bs, cols - 1), axis=1) \
        * bs + picked % bs
    return _sa.picked_attention(q, k_pool, v_pool, rows,
                                jnp.minimum(pos + 1, k), scale=scale)


def _sparse_gate(plane_shapes, rows, queries, heads, dtype):
    k = plane_shapes["k"]
    return _sparse_kernel((rows, queries, heads, k[-1]), k,
                          plane_shapes["ik"], dtype)


def sparse_blocks_read(lens, table_cols, plane_shapes, rows, heads, dtype,
                       queries=1):
    """As `paged_blocks_read`, for a `sparse_paged_attention` call: the
    blocks the indexer's walk reads of the `ik` plane (a prefill chunk's
    attention walks the same blocks of K and V; a decode step's reads
    positions, not blocks: `sparse_positions_read`), or every column of
    every row's table where the XLA form gathers."""
    if _sparse_gate(plane_shapes, rows, queries, heads, dtype) is None:
        return len(lens) * table_cols
    return _pa.walked_blocks(lens, table_cols, plane_shapes["k"][1],
                             queries=queries)


def sparse_positions_read(lens, table_cols, plane_shapes, rows, heads, dtype,
                          queries=1, real=1, topk=None, **_):
    """What the indexer scores and the attention reads, in positions, for
    rows whose last query sees `lens` positions, `real` real queries a
    row.  A decode step (`queries` 1), summed over its rows:
    `indexer_positions` (scored: what a query sees) and
    `selected_positions` (the K/V positions the path that serves reads:
    the picks, at most `topk` a row, or every table position where the
    XLA form gathers).  A prefill chunk, over (query, position) pairs:
    `scored_pairs`, `selected_pairs` (at most `topk` a query) and
    `attended_pairs` (what its attention computes over: every visible
    pair in the kernel's walk, every table position in the XLA form).
    The op's other arguments are its walk's (`sparse_walk_copies`)."""
    kernel = _sparse_gate(plane_shapes, rows, queries, heads,
                          dtype) is not None
    table = table_cols * plane_shapes["k"][1]
    scored = selected = attended = 0
    for n in lens:
        seen = range(int(n) - real + 1, int(n) + 1)
        scored += sum(seen)
        selected += sum(min(m, topk) for m in seen)
        attended += len(seen) * table
    if queries == 1:
        return dict(indexer_positions=scored,
                    selected_positions=selected if kernel else attended)
    return dict(scored_pairs=scored, selected_pairs=selected,
                attended_pairs=scored if kernel else attended)


def sparse_walk_copies(tables, pos, plane_shapes, rows, heads, dtype,
                       queries=1, index_heads=1, **_):
    """`ik_copies`: the DMAs the indexer's walk issues for a call sent
    `tables` and `pos` (`sparse_attention.walk_copies`: one a run of
    adjacent blocks in a chunk, in power-of-two pieces), where the
    kernel serves; nothing where the XLA form gathers."""
    if _sparse_gate(plane_shapes, rows, queries, heads, dtype) is None:
        return {}
    return {"ik_copies": _sa.walk_copies(
        tables, pos, plane_shapes["ik"][1], queries=queries,
        heads=index_heads)}


override("sparse_paged_attention", sparse_paged_attention_with_pallas)


_BLOCKS_READ = {"paged_attention": paged_blocks_read,
                "latent_paged_attention": latent_blocks_read}
# the readers of ops whose planes differ: each takes them by name
_PLANES_READ = {"sparse_paged_attention": sparse_blocks_read}
_POSITIONS_READ = {"sparse_paged_attention": sparse_positions_read}
# the ops whose walk copies runs of adjacent blocks, and the rule of each
_WALK_COPIES = {"sparse_paged_attention": sparse_walk_copies}


def pool_blocks_read(op, lens, table_cols, plane_shapes, rows, heads, dtype,
                     window=None, queries=1):
    """Pool blocks one layer of a serving program of `rows` slots,
    `queries` query tokens a slot (a decode program: 1; a prefill
    program: its bucket), reads for rows whose last query sees `lens`
    positions, by the registered op `op` that the model says reads its
    planes (`plane_shapes`: {name: one layer's array shape}).
    `window`: the layer's band, where its op takes one.  An op of
    `_BLOCKS_READ` reads planes that are alike, so that one stands for
    all (its last axis the width a query meets); an op whose planes
    differ needs a reader of `_PLANES_READ`, which takes them by name."""
    if op in _PLANES_READ:
        return _PLANES_READ[op](lens, table_cols, plane_shapes, rows, heads,
                                dtype, queries=queries)
    shapes = set(plane_shapes.values())
    if len(shapes) != 1:
        raise ValueError(
            f"the planes {plane_shapes} of {op!r} differ and no reader of "
            f"_PLANES_READ says how it reads them")
    shape = shapes.pop()
    band = {} if window is None else {"window": window}
    return _BLOCKS_READ[op](lens, table_cols,
                            (rows, queries, heads, shape[-1]), shape, dtype,
                            **band)


def pool_positions_read(op, lens, table_cols, plane_shapes, rows, heads,
                        dtype, queries=1, real=1, **op_args):
    """The positions counts of an op that picks what it reads
    (`_POSITIONS_READ`, e.g. `sparse_positions_read`), by the path that
    serves the shapes; {} for every other op.  `op_args`: what the model
    says the op's counts need (`cache_op_args`)."""
    reader = _POSITIONS_READ.get(op)
    if reader is None:
        return {}
    return reader(lens, table_cols, plane_shapes, rows, heads, dtype,
                  queries=queries, real=real, **op_args)


def pool_walk_copies(op, tables, pos, plane_shapes, rows, heads, dtype,
                     queries=1, **op_args):
    """The DMA counts of an op whose walk copies runs of adjacent pool
    blocks (`_WALK_COPIES`, e.g. `sparse_walk_copies`) for one call of
    `rows` rows, `queries` query tokens a row, sent `tables` and first
    positions `pos` (host arrays), by the path that serves the shapes;
    {} for every other op.  `op_args`: what the model says the op's
    counts need (`cache_op_args`)."""
    rule = _WALK_COPIES.get(op)
    if rule is None:
        return {}
    return rule(tables, pos, plane_shapes, rows, heads, dtype,
                queries=queries, **op_args)


_xla_grouped_matmul = get("grouped_matmul").fn
_GMM_ROWS = 128             # the kernel's row tile
_GMM_BLOCK_BYTES = 6 << 20  # one weight block in VMEM (two are in flight)


def _gmm_tiling(k, n, itemsize):
    """(row, k, n) tile sizes of the grouped-matmul kernel, or None where
    the shapes are not whole lane tiles: all of k and as much of n (in
    multiples of 128 that divide it) as keeps one weight block under
    `_GMM_BLOCK_BYTES`, so that a group's matrix is read once a row tile
    and the product is bound by that read."""
    if k % 128 or n % 128 or k * 128 * itemsize > _GMM_BLOCK_BYTES:
        return None
    tn = max(t for t in range(128, n + 1, 128)
             if n % t == 0 and k * t * itemsize <= _GMM_BLOCK_BYTES)
    return _GMM_ROWS, k, tn


def grouped_matmul_with_pallas(rows, weights, group_sizes):
    """On TPU the grouped products of the dropless expert layer run
    through JAX's tiled grouped-matmul kernel (`megablox.gmm`, pallas:
    row tiles visit only the groups they overlap, a group's weights
    stream through VMEM once a tile), which XLA's own ragged product
    trails 3-4 x at the expert shapes (PERF.md, PR 28).  Rows are padded
    to whole row tiles; the pad rows belong to no group and are cut off.
    Anything else (CPU, a fleet mesh, unaligned widths, other dtypes)
    keeps `jax.lax.ragged_dot`."""
    mode = _mode()
    tiling = _gmm_tiling(weights.shape[1], weights.shape[2],
                         weights.dtype.itemsize)
    if mode is None or tiling is None or _mesh_split() is not None \
            or rows.dtype != weights.dtype \
            or rows.dtype not in (jnp.float32, jnp.bfloat16):
        return _xla_grouped_matmul(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = rows.shape[0]
    pad = -m % _GMM_ROWS
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, weights, group_sizes.astype(jnp.int32),
              rows.dtype, tiling, None, None, False, mode == "interpret")
    return out[:m] if pad else out


override("grouped_matmul", grouped_matmul_with_pallas)


_xla_kda_step = get("kda_step").fn


def kda_step_with_pallas(q, k, v, g, beta, state, slots, live):
    """On TPU a decode step's recurrent states are updated where they lie
    in the pool (ops/pallas/kda.py); the XLA gather-update-scatter keeps
    everything else (CPU, a fleet mesh, head sizes other than 128)."""
    mode = _mode()
    if mode is None or _mesh_split() is not None \
            or state.dtype != jnp.float32 \
            or not _kda.supports(q.shape, state.shape):
        return _xla_kda_step(q, k, v, g, beta, state, slots, live)
    return _kda.kda_decode_step(q, k, v, g, beta, state, slots, live,
                                interpret=(mode == "interpret"))


override("kda_step", kda_step_with_pallas)
