"""Pallas TPU attention over a LATENT paged pool: a decode step's kernel
and a prefill chunk's.

A latent-attention model caches one row a token a layer, ``[c | k_rope |
zeros]`` of width W (a multiple of 128 lanes), and its decode step
absorbs the up-projections into the query: every head scores its
absorbed query against the SAME cached row (all W columns) and sums the
SAME row's first `value_dim` columns (``c``).  So the pool is
[num_blocks, block_size, W], one block is one aligned tile, and both
contractions are MXU dots over a chunk of cached tokens with every head
at once: ``[H, W] . [W, tokens]`` and ``[H, tokens] . [tokens,
value_dim]``, accumulated in float32.

The walk is `paged_attention.py`'s (PERF.md, PR 26): the grid is the
batch's rows, the pool stays in HBM (`memory_space=ANY`), one program
walks ITS row's live context, `cdiv(lens[b], block_size)` blocks and not
a column more, in chunks of `chunk_blocks()` pool blocks that the
kernel's own DMAs copy through the table into one of two VMEM buffers
while the other is reduced; a row's last chunk starts the next row's
first.  A dead slot (length 1) costs one block (`walked_blocks()` is
that count, for the engine's spans).  Columns of a chunk past the row's
length hold blocks of an earlier chunk or row (the buffers are zeroed
once, so they are finite) and are masked out of the softmax.

A PREFILL CHUNK (more than one absorbed query row a request) has a
kernel of its own, `latent_paged_prefill_attention`, on the walk of the
K/V prefill kernel (`paged_attention.PrefillWalk`): a program takes a
TILE of query positions (`prefill_tile`), walks the blocks that tile
sees (whole chunks above the causal diagonal are skipped, not masked)
in this module's chunks of `chunk_blocks`, two buffers deep.  Its body
is this module's, not the K/V kernel's: there are no kv heads to take
apart, so the tile's rows (position, head) as they lie in q, tq x H of
them, reduce a copied chunk with two products, ``[tq H, W] . [W,
keys]`` and ``[tq H, keys] . [keys, value_dim]``, online softmax in
float32 scratch, p rounded to the pool's dtype as the decode kernel and
the XLA reference round it.  What the pool holds where no position of
the request lies (block 0, the positions under a bucket's padding rows)
is cleared where it is not finite before it is multiplied.  The shape
chooses the kernel (`supports`): nothing else does.  The XLA gather of
`latent_paged_attention_k` (ops/nn_kernels.py) is the fallback and the
parity reference of both kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

_NEG_INF = float("-inf")
_LANES = 128
_CHUNK_TOKENS = 512     # cached tokens reduced per step of the walk
_PREFILL_VMEM = 112 << 20   # of the v5e's 128 MiB, for one prefill program
# (position, head) rows one prefill program holds: 256 positions at 16
# heads.  Kernel alone on the v5e at the latent cell's 1,024-row chunk,
# 4,096 rows against 8,192 (the K/V kernel's 512 positions): 0.244 /
# 0.286 ms at a context of 0, 1.213 / 1.227 at 4,500, 3.190 / 3.204 at
# 14,000, in 48 MiB of VMEM against 93 and a third of the compile time
# (PERF.md, PR 38)
_TILE_ROWS = 4096


def chunk_blocks(table_cols, block_size):
    """Pool blocks the kernel copies per step of its walk: `_CHUNK_TOKENS`
    cached tokens, enough columns for the two dots to fill the MXU's
    width several times over while both buffers of W = 640 bf16 lanes
    stay at 1.3 MiB of VMEM."""
    return max(1, min(table_cols, _CHUNK_TOKENS // block_size))


def walked_blocks(lens, table_cols, block_size, queries=1):
    """Pool blocks the kernels copy and reduce for rows whose LAST query
    sees `lens` positions (host numbers; a decode row has one query, a
    prefill chunk `queries` of them): the K/V kernels' rule without a
    band (`paged_attention.walked_blocks`).  A row's walk ends with the
    block that holds its last position, a dead slot (length 1) walks one
    block, and no row walks past its table."""
    return _pa.walked_blocks(lens, table_cols, block_size, queries=queries)


def _decode_kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sems,
                   slot_s, *, bs, chunk, value_dim, scale):
    b = pl.program_id(0)
    rows, cols = tables_ref.shape
    tokens = chunk * bs

    def visible(row):
        return jnp.maximum(lens_ref[row], 1)

    def blocks(row):            # the rule `walked_blocks` states
        return jnp.minimum(pl.cdiv(visible(row), bs), cols)

    def copies(row, i, slot, act):
        """`act` on the copy of every block of the row's i-th chunk that
        the row lives in; returns how many those are."""
        first = i * chunk
        n = jnp.minimum(blocks(row) - first, chunk)

        def one(c, _):
            blk = tables_ref[row, first + c]
            act(pltpu.make_async_copy(
                pool_hbm.at[blk],
                buf.at[slot, pl.ds(pl.multiple_of(c * bs, bs), bs)],
                sems.at[slot]))
            return _

        lax.fori_loop(0, n, one, 0)
        return n

    def start(row, i, slot):
        copies(row, i, slot, lambda dma: dma.start())

    @pl.when(b == 0)
    def _first():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_s[0] = 0
        start(0, 0, 0)

    length = visible(b)
    n_chunks = pl.cdiv(blocks(b), chunk)
    q = q_ref[0]                                            # (H, W)
    heads = q.shape[0]

    def reduce_chunk(i, carry):
        slot, m_prev, l_prev, acc = carry
        # the next chunk flies while this one is reduced: this row's,
        # or after its last the next row's first
        last = i + 1 == n_chunks
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < rows)
        def _prefetch():
            start(nxt_row, jnp.where(last, 0, i + 1), 1 - slot)

        copies(b, i, slot, lambda dma: dma.wait())
        rows_kv = buf[slot]                                 # (tokens, W)
        s = lax.dot_general(q, rows_kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = i * tokens + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)            # (H, tokens)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                              # masked -> 0
        corr = jnp.exp(m_prev - m_new)
        pv = lax.dot_general(p.astype(rows_kv.dtype),
                             rows_kv[:, :value_dim],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return (1 - slot, m_new,
                l_prev * corr + p.sum(axis=1, keepdims=True),
                acc * corr + pv)

    init = (slot_s[0], jnp.full((heads, 1), _NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, value_dim), jnp.float32))
    slot, _, l, acc = lax.fori_loop(0, n_chunks, reduce_chunk, init)
    slot_s[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_paged_decode_attention(q, pool, tables, lens, value_dim,
                                  scale=None, interpret=False):
    """One-token attention of absorbed queries over a latent pool.
    q: [B, 1, H, W]; pool: [N, bs, W]; tables: [B, M] int32 block ids;
    lens: [B] int32 visible context length INCLUDING the token just
    written (a row of length 0 is walked as a dead slot is, and its
    output means nothing).  Returns [B, 1, H, value_dim] in q's dtype.
    `scale` is a host number (None: 1 / sqrt(W)), fixed at trace time."""
    B, s, H, W = q.shape
    if s != 1:
        raise ValueError("latent_paged_decode_attention is decode-only")
    if pool.shape[2] != W or not 0 < value_dim <= W:
        raise ValueError(
            f"queries of width {W} and values of {value_dim} do not fit "
            f"pool rows of {pool.shape[2]}")
    scale = float(scale) if scale is not None else W ** -0.5
    return _latent_decode(q, pool, tables, lens, value_dim=int(value_dim),
                          scale=scale, interpret=bool(interpret))


# jitted, so that a model's layers trace and lower ONE kernel
@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def _latent_decode(q, pool, tables, lens, *, value_dim, scale, interpret):
    B, _, H, W = q.shape
    _, bs, _ = pool.shape
    M = tables.shape[1]
    chunk = chunk_blocks(M, bs)
    kernel = functools.partial(_decode_kernel, bs=bs, chunk=chunk,
                               value_dim=value_dim, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, tables_ref, lens_ref:
                         (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, H, value_dim), lambda b, tables_ref, lens_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        # a row hands the next its first chunk in flight: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_decode_attention",
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q[:, 0], pool)
    return out[:, None]


# ---------------------------------------------------------------- prefill
def _prefill_kernel(tables_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sems,
                    m_ref, l_ref, acc_ref, *, bs, chunk, heads, tq,
                    value_dim, scale):
    """Written in `lax` where `jnp` would do, as the K/V prefill body
    is: a serving process traces and lowers it for every bucket before
    its first request."""
    walker = _pa.PrefillWalk(tables_ref, pos_ref, tq=tq, bs=bs,
                             chunk=chunk, window=None)
    rows = q_ref.shape[1]                       # (position, head)
    keys = chunk * bs
    dtype = buf.dtype
    exact = dtype == jnp.float32
    precision = lax.Precision.HIGHEST if exact else None
    f32 = jnp.float32

    def copy_block(blk, c, slot, start):
        dma = pltpu.make_async_copy(
            pool_hbm.at[blk], buf.at[slot, pl.ds(pl.multiple_of(c * bs, bs),
                                                 bs)], sems.at[slot])
        dma.start() if start else dma.wait()

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    # the position of a query row, and of a chunk's column
    q_pos = walker.p0 + lax.broadcasted_iota(jnp.int32, (rows, keys), 0) \
        // heads
    col = lax.broadcasted_iota(jnp.int32, (rows, keys), 1)

    def reduce_chunk(i, slot):
        # a masked column's p is 0, and 0 x NaN is NaN in a product: what
        # no copy wrote, and what the pool holds under a bucket's padding
        # rows and in block 0, is cleared before anything multiplies it
        kv = buf[slot]                                      # (keys, W)
        kv = lax.select(lax.le(lax.abs(lax.convert_element_type(kv, f32)),
                               jnp.finfo(f32).max), kv, lax.full_like(kv, 0))
        s = lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                            precision=precision, preferred_element_type=f32)
        live = lax.le(lax.add(col, i * keys), q_pos)
        s = lax.select(live, lax.mul(s, f32(scale)),
                       lax.full(s.shape, _NEG_INF, f32))    # (rows, keys)
        m_prev = m_ref[...]
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(s, (1,)),
                                                (1,)))
        # a row that has seen nothing yet: exp(-inf - 0) is 0
        m_at = lax.select(lax.eq(m_new, _NEG_INF),
                          lax.full_like(m_new, 0), m_new)
        p = lax.exp(lax.sub(s, lax.broadcast_in_dim(m_at, s.shape, (0, 1))))
        corr = lax.exp(lax.sub(m_prev, m_at))               # masked p: 0
        l_ref[...] = lax.add(lax.mul(l_ref[...], corr),
                             lax.expand_dims(lax.reduce_sum(p, (1,)), (1,)))
        # p rounded to the pool's dtype, as the XLA reference rounds it
        acc_ref[...] = lax.add(
            lax.mul(acc_ref[...],
                    lax.broadcast_in_dim(corr, acc_ref.shape, (0, 1))),
            lax.dot_general(lax.convert_element_type(p, dtype),
                            lax.slice(kv, (0, 0), (keys, value_dim)),
                            (((1,), (0,)), ((), ())),
                            precision=precision, preferred_element_type=f32))
        m_ref[...] = m_new

    walker.run(copy_block, reduce_chunk)

    o_ref[0] = lax.convert_element_type(
        lax.div(acc_ref[...],
                lax.broadcast_in_dim(l_ref[...], acc_ref.shape, (0, 1))),
        o_ref.dtype)


def latent_paged_prefill_attention(q, pool, tables, pos, value_dim,
                                   scale=None, interpret=False):
    """Attention of a chunk of `s` absorbed query rows a request over a
    latent pool.  q: [B, s, H, W]; pool: [N, bs, W]; tables: [B, M]
    int32 block ids; pos: [B] int32, the context offset of a row's
    FIRST query: row i sees the positions ``<= pos + i``, and the
    chunk's own rows are in the pool already.  A request's walk reads
    the blocks ``0 .. cdiv(pos + s, bs) - 1`` of its table, clipped to
    its columns, and nothing else.  The kernel carries the name
    ``latent_paged_prefill_attention`` in a device trace.  Returns
    [B, s, H, value_dim] in q's dtype."""
    W = q.shape[-1]
    if not supports(q.shape, pool.shape, value_dim, q.dtype) \
            or q.shape[1] < 2:
        raise ValueError(
            f"latent_paged_prefill_attention does not serve q {q.shape} "
            f"over a pool {pool.shape} of {q.dtype} with values of "
            f"{value_dim}; the XLA fallback does")
    scale = float(scale) if scale is not None else W ** -0.5
    return _latent_prefill(q, pool, tables, pos, value_dim=int(value_dim),
                           scale=scale, interpret=bool(interpret))


# jitted as `_latent_decode` is: every layer of a bucket traces and
# lowers ONE kernel
@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def _latent_prefill(q, pool, tables, pos, *, value_dim, scale, interpret):
    B, s, H, W = q.shape
    _, bs, _ = pool.shape
    M = tables.shape[1]
    chunk = chunk_blocks(M, bs)
    tq = prefill_tile(s, H, W, value_dim, chunk * bs, q.dtype)
    rows = tq * H
    kernel = functools.partial(_prefill_kernel, bs=bs, chunk=chunk,
                               heads=H, tq=tq, value_dim=value_dim,
                               scale=scale)
    # the rows (position, head) of a chunk are the queries as they lie
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, s // tq),
        in_specs=[
            pl.BlockSpec((1, rows, W), lambda b, t, tables_ref, pos_ref:
                         (b, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, rows, value_dim), lambda b, t, tables_ref, pos_ref:
            (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, value_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, s * H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=prefill_vmem(rows, W, value_dim, chunk * bs,
                                          q.dtype)),
        interpret=interpret,
        name="latent_paged_prefill_attention",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, s * H, W), pool)
    return out.reshape(B, s, H, value_dim)


def prefill_vmem(rows, width, value_dim, keys, dtype):
    """Bytes of VMEM a prefill program of `rows` (position, head) rows
    scopes: its query and output blocks two deep, its accumulators (m
    and l padded to a lane tile each, the float32 sum of values), the
    walk's two buffers, and a chunk's scores twice over in float32 (s
    and p live together), with an eighth to spare; a float32 pool's
    products at the highest precision hold their row operands twice
    more, in bfloat16 pieces.  Mosaic asked for 93.3 MiB at 8,192 rows
    of 640 over 512 keys in bfloat16 (this says 104) and for 109.5 MiB
    at 4,096 in float32 (this says 115)."""
    item = jnp.dtype(dtype).itemsize
    per_row = 2 * (width + value_dim) * item \
        + 4 * (value_dim + 2 * _LANES) + 2 * 4 * keys
    if item == 4:
        per_row += 2 * (width + value_dim) * item
    need = rows * per_row + 2 * keys * width * item
    return need + need // 8


def prefill_tile(s, heads, width, value_dim, keys, dtype):
    """Query positions one program of the prefill kernel attends: the
    K/V kernel's rule (`paged_attention.prefill_tile`) at `_TILE_ROWS`
    rows, halved while its VMEM would pass `_PREFILL_VMEM` (and while
    the halves stay whole 16-row tiles)."""
    tq = _pa.prefill_tile(s, heads, _TILE_ROWS)
    while prefill_vmem(tq * heads, width, value_dim, keys, dtype) \
            > _PREFILL_VMEM and tq % 32 == 0:
        tq //= 2
    return tq


def supports(q_shape, pool_shape, value_dim, dtype, mp=1):
    """Shape/dtype gate for the pallas path; anything else keeps the XLA
    gather (which is also the numerics reference).  `mp` is the number
    of head shards the fleet mesh cuts the call into (the pool's rows
    serve every head, so only the queries split).  One query row a
    request (`q_shape[1] == 1`) is the decode kernel's, more are the
    prefill kernel's: the shape chooses, nothing else does."""
    if len(q_shape) != 4 or q_shape[1] < 1:
        return False
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False        # Mosaic: "Invalid vector type for load" (f16)
    s, H, W = q_shape[1:]
    if len(pool_shape) != 3 or pool_shape[2] != W:
        return False
    if W % _LANES or value_dim % _LANES or not 0 < value_dim <= W:
        return False        # a row is whole lane tiles, and so is c
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    if pool_shape[1] % sublanes:
        return False        # a block is whole sublane tiles of the buffer
    if H % mp or (H // mp) % 8:
        return False
    if s > 1:
        # a chunk that cannot be halved into tiles has to fit whole,
        # beside the most keys a step of the walk holds
        keys = chunk_blocks(_CHUNK_TOKENS, pool_shape[1]) * pool_shape[1]
        tq = prefill_tile(s, H // mp, W, value_dim, keys, dtype)
        if prefill_vmem(tq * H // mp, W, value_dim, keys, dtype) \
                > _PREFILL_VMEM:
            return False
    return True
