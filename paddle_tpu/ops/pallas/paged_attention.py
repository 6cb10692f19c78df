"""Pallas TPU paged decode-attention — the serving hot path.

One decode step attends a request's whole context through its block
table: the KV pool lives as [num_blocks, block_size, Hkv, D] arrays and
each request's program walks its table one block at a time with online
softmax, never materializing a contiguous KV window (the jnp fallback
`paged_attention` in ops/nn_kernels.py gathers; this kernel streams).
CuBridge (arXiv:2605.05023) is the PAPERS.md reference for
reconstructing this class of paged attention kernel; the scalar-
prefetch block-table indexing follows the vLLM/TPU pattern — the table
and per-row lengths are `PrefetchScalarGridSpec` scalar args, so the
block index map can route each grid step's DMA to the right pool block
before the kernel body runs.

Block shapes: the TPU lowering wants the last two dims of every block
to be multiples of (8, 128) or the whole array dim, and the pool keeps
heads in the second-minor position — so one program takes ALL kv heads
of one pool block, `(1, bs, Hkv, D)`, and all q heads of its row.  With
one query token there is no matmul worth the MXU: scores and the p·v
sum are broadcast-multiply-reduce on the VPU over the block as it lies
in the pool (token on the leading axis, head on sublanes, D on lanes),
so no block is transposed or copied.

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


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_s, l_s, acc_s, *, bs, nblk, g, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = lens_ref[b]

    # blocks entirely past the row's context are skipped (their DMA still
    # lands — the table pads with block 0 — but no FLOPs are spent)
    @pl.when(j * bs < length)
    def _body():
        k = k_ref[0].astype(jnp.float32)                   # (bs, Hkv, D)
        v = v_ref[0].astype(jnp.float32)
        cols = j * bs + lax.broadcasted_iota(
            jnp.int32, (bs, k.shape[1], 1), 0)
        live = cols < length                               # (bs, Hkv, 1)
        for gi in range(g):
            q = q_ref[0, gi].astype(jnp.float32)           # (Hkv, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(live, s, _NEG_INF)               # (bs, Hkv, 1)
            m_prev = m_s[gi][:, :1]                        # (Hkv, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=0))
            p = jnp.exp(s - m_new[None])                   # masked -> 0
            corr = jnp.exp(m_prev - m_new)
            l_new = l_s[gi][:, :1] * corr + p.sum(axis=0)
            acc_s[gi] = acc_s[gi] * corr + jnp.sum(p * v, axis=0)
            l_s[gi] = jnp.broadcast_to(l_new, l_s.shape[1:])
            m_s[gi] = jnp.broadcast_to(m_new, m_s.shape[1:])

    @pl.when(j == nblk - 1)
    def _emit():
        l = l_s[...][:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lens, scale=None,
                           interpret=False):
    """One-token paged attention.  q: [B, 1, H, D]; pools:
    [N, bs, Hkv, D]; tables: [B, M] int32 block ids; lens: [B] int32
    visible context length (INCLUDING the token just written).
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
    N, bs, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    g = H // Hkv
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    # head h = hk * g + gi  ->  [B, g, Hkv, D]
    qb = q.reshape(B, Hkv, g, D).swapaxes(1, 2)

    kernel = functools.partial(_decode_kernel, bs=bs, nblk=M, g=g,
                               scale=scale)
    kv_spec = pl.BlockSpec(
        (1, bs, Hkv, D),
        lambda b, j, tables_ref, lens_ref: (tables_ref[b, j], 0, 0, 0))
    q_spec = pl.BlockSpec(
        (1, g, Hkv, D), lambda b, j, tables_ref, lens_ref: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, Hkv, _LANES), jnp.float32),
            pltpu.VMEM((g, Hkv, _LANES), jnp.float32),
            pltpu.VMEM((g, Hkv, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, g, Hkv, D), q.dtype),
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
