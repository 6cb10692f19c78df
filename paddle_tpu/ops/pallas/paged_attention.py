"""Pallas TPU paged decode-attention — the serving hot path.

One decode step attends a request's whole context through its block
table: the KV pool lives as [num_blocks, block_size, Hkv, D] arrays and
the kernel streams a row's blocks through VMEM with online softmax,
never materializing a contiguous KV window (the jnp fallback
`paged_attention` in ops/nn_kernels.py gathers; this kernel streams).
CuBridge (arXiv:2605.05023) is the PAPERS.md reference for
reconstructing this class of paged attention kernel; the block table
and the per-row lengths are `PrefetchScalarGridSpec` scalar args, as in
the vLLM/TPU pattern.

The walk is per row and ragged.  The grid is the batch's rows; the
pools stay in HBM (`memory_space=ANY`) and one program walks ITS row's
live context, `cdiv(lens[b], block_size)` blocks and not a column more,
in chunks of `chunk_blocks()` pool blocks: the kernel's own DMAs copy a
chunk's blocks through the table into one of two VMEM buffers while the
other is reduced, and a row's last chunk starts the next row's first.
So the time follows the live blocks of the live rows, not slots x table
columns: a dead slot (length 1) costs one block, a table column past a
row's context nothing (`walked_blocks()` is that count, for the
engine's spans).  On the v5e a 16-token block of 16 heads costs 0.27 us
in a chunk of 8 against 0.41 us copied alone (PERF.md, PR 26).

Shapes: the pool keeps heads in the second-minor position, so a block
`(bs, Hkv, D)` lands in VMEM as it lies in the pool (token on the
leading axis, head on sublanes, D on lanes) and no block is transposed.
With one query token there is no matmul worth the MXU: scores and the
p·v sum are broadcast-multiply-reduce on the VPU in float32, block by
block, the running max, sum and accumulator carried in registers.

Decode-only (q seq len 1) and lane-aligned head dims only (D % 128 ==
0; the pool is the replica's whole KV memory, so in-call padding would
copy it per layer per step): prefill chunks and other head dims keep
the XLA gather fallback, whose masked-sdpa math is the parity
reference.  GQA: q head h reads kv head h // (H // Hkv); q is laid out
[B, g, Hkv, D] for the kernel (a transpose of the one q token, never
of the pool) so each group member is one (Hkv, D) tile.
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
_CHUNK_BYTES = 512 * 1024       # one operand's chunk, as it lies in HBM
_CHUNK_VMEM = 1024 * 1024       # ... and at most, as it lies in VMEM


def chunk_blocks(table_cols, block_size, kv_heads, head_dim, dtype):
    """Pool blocks the kernel copies per step of its walk (`C`): as
    many as make one operand's copy about half a MiB, so that a step's
    DMAs are worth their set-up, while K and V, double-buffered, stay a
    quarter of the 16 MiB of VMEM a kernel may scope.  In VMEM the head
    axis is padded to whole sublane tiles, which binds where GQA or an
    `mp` shard leaves few kv heads."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    row = block_size * head_dim * itemsize
    in_vmem = row * -(-kv_heads // sublanes) * sublanes
    return max(1, min(table_cols, _CHUNK_BYTES // (row * kv_heads),
                      _CHUNK_VMEM // in_vmem))


def walked_blocks(lens, table_cols, block_size):
    """Pool blocks the kernel copies and reduces for rows of visible
    lengths `lens` (host numbers): a row's walk ends with the block
    that holds its last position (of its last chunk only the blocks it
    lives in are copied, so the chunk does not enter), a dead slot
    (length 1) walks one block, and no row walks past its table."""
    return sum(min(-(-max(int(n), 1) // block_size), table_cols)
               for n in lens)


def _decode_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, slot_s, *, bs, chunk, g, scale):
    b = pl.program_id(0)
    rows, cols = tables_ref.shape

    def visible(row):
        return jnp.maximum(lens_ref[row], 1)

    def blocks(row):            # the rule `walked_blocks` states
        return jnp.minimum(pl.cdiv(visible(row), bs), cols)

    def copies(row, i, slot, act):
        """`act` on the copy of every block of the row's i-th chunk
        that the row lives in; returns how many those are."""
        first = i * chunk
        n = jnp.minimum(blocks(row) - first, chunk)

        def one(c, _):
            blk = tables_ref[row, first + c]
            act(pltpu.make_async_copy(
                k_hbm.at[blk], k_buf.at[slot, c], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[blk], v_buf.at[slot, c], sems.at[1, slot]))
            return _

        lax.fori_loop(0, n, one, 0)
        return n

    def start(row, i, slot):
        copies(row, i, slot, lambda dma: dma.start())

    @pl.when(b == 0)
    def _first():
        slot_s[0] = 0
        start(0, 0, 0)

    length = visible(b)
    n_chunks = pl.cdiv(blocks(b), chunk)
    hkv, d = q_ref.shape[2:]
    qs = [q_ref[0, gi].astype(jnp.float32) for gi in range(g)]  # (Hkv, D)

    def reduce_chunk(i, carry):
        slot, state = carry
        # the next chunk flies while this one is reduced: this row's,
        # or after its last the next row's first
        last = i + 1 == n_chunks
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < rows)
        def _prefetch():
            start(nxt_row, jnp.where(last, 0, i + 1), 1 - slot)

        n = copies(b, i, slot, lambda dma: dma.wait())

        def reduce_block(c, state):
            k = k_buf[slot, c].astype(jnp.float32)         # (bs, Hkv, D)
            v = v_buf[slot, c].astype(jnp.float32)
            pos = (i * chunk + c) * bs + lax.broadcasted_iota(
                jnp.int32, (bs, hkv, 1), 0)
            live = pos < length                            # (bs, Hkv, 1)
            out = []
            for q, (m_prev, l_prev, acc) in zip(qs, state):
                s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
                s = jnp.where(live, s, _NEG_INF)           # (bs, Hkv, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=0))  # (Hkv, 1)
                p = jnp.exp(s - m_new[None])               # masked -> 0
                corr = jnp.exp(m_prev - m_new)
                out.append((m_new, l_prev * corr + p.sum(axis=0),
                            acc * corr + jnp.sum(p * v, axis=0)))
            return tuple(out)

        return 1 - slot, lax.fori_loop(0, n, reduce_block, state)

    init = tuple((jnp.full((hkv, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((hkv, 1), jnp.float32),
                  jnp.zeros((hkv, d), jnp.float32)) for _ in qs)
    slot, state = lax.fori_loop(0, n_chunks, reduce_chunk,
                                (slot_s[0], init))
    slot_s[0] = slot
    for gi, (_, l, acc) in enumerate(state):
        o_ref[0, gi] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lens, scale=None,
                           interpret=False):
    """One-token paged attention.  q: [B, 1, H, D]; pools:
    [N, bs, Hkv, D]; tables: [B, M] int32 block ids; lens: [B] int32
    visible context length, INCLUDING the token just written, so at
    least 1: a row of length 0 is walked as a dead slot is, over
    position 0 of its first block, and its output means nothing.
    `scale` is a host number (None: 1 / sqrt(D)), fixed at trace time.
    Returns [B, 1, H, D] in the q dtype."""
    B, s, H, D = q.shape
    if s != 1:
        raise ValueError("paged_decode_attention is decode-only (s == 1)")
    if D % _LANES:
        # never pad the POOL here — it is the replica's whole KV memory,
        # and an in-call jnp.pad would copy it per layer per step.
        # supports() routes these shapes to the XLA gather fallback.
        raise ValueError(
            f"paged_decode_attention needs head_dim % {_LANES} == 0 "
            f"(got {D}); the XLA fallback serves other head dims")
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    return _paged_decode(q, k_pool, v_pool, tables, lens, scale=scale,
                         interpret=bool(interpret))


# jitted, so that a model's layers trace and lower ONE kernel: the walk
# with its loops and copies costs 0.05 s a call site to trace
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_decode(q, k_pool, v_pool, tables, lens, *, scale, interpret):
    B, _, H, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    g = H // Hkv
    # head h = hk * g + gi  ->  [B, g, Hkv, D]
    qb = q.reshape(B, Hkv, g, D).swapaxes(1, 2)
    chunk = chunk_blocks(M, bs, Hkv, D, k_pool.dtype)

    kernel = functools.partial(_decode_kernel, bs=bs, chunk=chunk, g=g,
                               scale=scale)
    q_spec = pl.BlockSpec(
        (1, g, Hkv, D), lambda b, tables_ref, lens_ref: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, bs, Hkv, D), k_pool.dtype),
            pltpu.VMEM((2, chunk, bs, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, g, Hkv, D), q.dtype),
        # a row hands the next its first chunk in flight: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), lens.astype(jnp.int32),
      qb, k_pool, v_pool)
    return out.swapaxes(1, 2).reshape(B, 1, H, D)


def supports(q_shape, pool_shape, dtype, mp=1):
    """Shape/dtype gate for the pallas paged path; anything else keeps
    the jnp gather fallback (which is also the numerics reference).
    `mp` is the number of head shards the fleet mesh cuts the call into
    (kv heads must divide, so every GQA group stays on one shard)."""
    if len(q_shape) != 4 or q_shape[1] != 1:
        return False        # decode-only: prefill chunks use the fallback
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False        # Mosaic: "Invalid vector type for load" (f16)
    H, D = q_shape[2], q_shape[3]
    Hkv = pool_shape[2]
    if Hkv == 0 or H % Hkv or Hkv % mp:
        return False
    if D % _LANES:
        # lane-aligned head dims only (128: llama-7b/13b, gpt3-6.7B/13B,
        # qwen2-7b ...): padding the POOL per call would copy the whole
        # KV memory every step, so other dims keep the gather fallback
        return False
    return True
