"""Pallas TPU decode attention over a LATENT paged pool.

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

Decode-only (one query token a row).  Prefill chunks attend in the
expanded form, in XLA; the XLA gather of `latent_paged_attention_k`
(ops/nn_kernels.py) is the fallback and the parity reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128
_CHUNK_TOKENS = 512     # cached tokens reduced per step of the walk


def chunk_blocks(table_cols, block_size):
    """Pool blocks the kernel copies per step of its walk: `_CHUNK_TOKENS`
    cached tokens, enough columns for the two dots to fill the MXU's
    width several times over while both buffers of W = 640 bf16 lanes
    stay at 1.3 MiB of VMEM."""
    return max(1, min(table_cols, _CHUNK_TOKENS // block_size))


def walked_blocks(lens, table_cols, block_size):
    """Pool blocks the kernel copies and reduces for rows of visible
    lengths `lens` (host numbers): a row's walk ends with the block that
    holds its last position, a dead slot (length 1) walks one block, and
    no row walks past its table."""
    return sum(min(-(-max(int(n), 1) // block_size), table_cols)
               for n in lens)


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


def supports(q_shape, pool_shape, value_dim, dtype, mp=1):
    """Shape/dtype gate for the pallas path; anything else keeps the XLA
    gather (which is also the numerics reference).  `mp` is the number
    of head shards the fleet mesh cuts the call into (the pool's rows
    serve every head, so only the queries split)."""
    if len(q_shape) != 4 or q_shape[1] != 1:
        return False        # decode-only: prefill chunks attend expanded
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False        # Mosaic: "Invalid vector type for load" (f16)
    H, W = q_shape[2], q_shape[3]
    if len(pool_shape) != 3 or pool_shape[2] != W:
        return False
    if W % _LANES or value_dim % _LANES or not 0 < value_dim <= W:
        return False        # a row is whole lane tiles, and so is c
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    if pool_shape[1] % sublanes:
        return False        # a block is whole sublane tiles of the buffer
    if H % mp or (H // mp) % 8:
        return False
    return True
