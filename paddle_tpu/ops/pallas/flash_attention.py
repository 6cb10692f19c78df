"""Pallas TPU flash attention (fwd + bwd), the fused-attention hot op.

Reference parity: the reference exposes fused attention through
`paddle.nn.functional.scaled_dot_product_attention` backed by a CUDA
flash-attention kernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu).
Here the same op is a Pallas TPU kernel: blockwise online-softmax forward
and a two-kernel backward (dK/dV sweep + dQ sweep), designed around the
MXU (all matmuls are block matmuls with fp32 accumulation) and VMEM
(running max / denominator / accumulator live in scratch across the
innermost, sequential KV grid dimension).

Coverage (round 3): GQA (q heads grouped onto fewer kv heads via the
block index map — `repeat_interleave` semantics, no data duplication),
additive/boolean masks (full (…,Lq,Lk) and row-broadcast (…,1,Lk)
layouts), and ragged/non-block-divisible seq lens (inputs padded to the
block grid; padded key columns are masked inside the kernel, padded query
rows sliced off outside).  Fully-masked rows emit 0 (XLA's softmax gives
NaN there); `supports()` documents the remaining fallbacks.

Layout is (batch, seq, heads, head_dim) to match `sdpa` in
ops/nn_kernels.py; internally blocks run over a flattened (batch*heads)
leading grid axis.  Block sizes come from tuned_blocks.json next to this
file when present (written by `tools/pallas_tune.py --write` on chip,
keyed by the chip's `device_kind`);
otherwise 512/512 defaults.  Mask gradients are NOT produced by the
kernel — nn.functional routes grad-requiring masks to the XLA path.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128  # TPU vector lane count; scratch minor dims sized to this
_MASK_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


# ------------------------------------------------------------- tuned blocks
@functools.lru_cache(maxsize=1)
def _tuned_table():
    path = os.path.join(os.path.dirname(__file__), "tuned_blocks.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _default_blocks(D, Lq, Lk):
    """(bq, bk) from the tuned table; key: "device_kind|head_dim|seq"
    with the longest seq bucket ≤ max(Lq, Lk) winning.  Fallback
    512/512."""
    gen = jax.devices()[0].device_kind
    table = _tuned_table().get(gen, {}).get(str(D)) or \
        _tuned_table().get(gen, {}).get("*")
    if table:
        seq = max(Lq, Lk)
        best = None
        for bucket, bqbk in table.items():
            b = int(bucket)
            if b <= seq and (best is None or b > best[0]):
                best = (b, bqbk)
        if best is None:  # take the smallest bucket
            best = min(((int(b), v) for b, v in table.items()),
                       key=lambda t: t[0])
        return int(best[1][0]), int(best[1][1])
    return 512, 512


def _pad_to(n, b):
    return -(-n // b) * b


# ------------------------------------------------------------------ forward
def _fwd_kernel(*refs, scale, causal, off, bq, bk, nk, has_mask,
                mask_rows, lk_real, window):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        mask_ref = None
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = iq * bq
    k_start = ik * bk
    # bottom-right-aligned causal (row r attends cols <= r + Lk - Lq),
    # matching sdpa_k's jnp.tril(..., lk - lq)
    run = (q_start + bq + off > k_start) if causal else (ik >= 0)
    run = jnp.logical_and(run, k_start < lk_real)  # skip all-pad blocks
    if window:  # sliding window: skip blocks entirely left of the band
        run = jnp.logical_and(run,
                              k_start + bk - 1 > q_start + off - window)

    @pl.when(run)
    def _body():
        q = q_ref[0]                      # (bq, D) compute dtype
        k = k_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        cols = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = cols < lk_real
        if causal:
            rows = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            keep = jnp.logical_and(keep, rows + off >= cols)
            if window:  # attend cols in (r+off-window, r+off]
                keep = jnp.logical_and(keep, cols > rows + off - window)
        s = jnp.where(keep, s, _NEG_INF)
        if has_mask:
            m = mask_ref[0].astype(jnp.float32)   # (bq|1, bk) additive
            if mask_rows == 1:
                m = jnp.broadcast_to(m, (bq, bk))
            s = s + m
        m_prev = m_s[:, :1]               # (bq, 1) fp32
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)           # (bq, bk) fp32; masked cols -> 0
        corr = jnp.exp(m_prev - m_safe)   # (bq, 1)
        l_new = l_s[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * corr + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_s[:, :1] + jnp.log(l_safe)


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _kv_index(H, Hkv):
    """Map the flattened q BH index onto the kv BH index
    (repeat_interleave grouping: q head h reads kv head h // g)."""
    g = H // Hkv

    def f(b):
        return (b // H) * Hkv + (b % H) // g
    return f


def _mask_index(mask_meta, H):
    """Flattened-BH -> mask leading index.  Head- AND batch-broadcast are
    folded into the index map (no materialized copies)."""
    heads = mask_meta["heads"]
    batch1 = mask_meta.get("batch1", False)
    if heads == 1:
        return (lambda b: 0) if batch1 else (lambda b: b // H)
    return (lambda b: b % H) if batch1 else (lambda b: b)


def _fwd(q, k, v, mask, causal, scale, bq, bk, interpret, H, Hkv, mask_meta,
         lk_real, window=0):
    mask_meta = dict(mask_meta)
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    nq, nk = Lq // bq, Lk // bk
    has_mask = mask is not None
    mask_rows = 0 if not has_mask else mask_meta["rows"]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, off=mask_meta["off"],
        bq=bq, bk=bk, nk=nk, has_mask=has_mask, mask_rows=mask_rows,
        lk_real=lk_real, window=window)
    kvi = _kv_index(H, Hkv)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, _f=kvi: (_f(b), j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, _f=kvi: (_f(b), j, 0)),
    ]
    args = [q, k, v]
    if has_mask:
        mi = _mask_index(mask_meta, H)
        if mask_rows == 1:
            in_specs.append(pl.BlockSpec(
                (1, 1, bk), lambda b, i, j, _f=mi: (_f(b), 0, j)))
        else:
            in_specs.append(pl.BlockSpec(
                (1, bq, bk), lambda b, i, j, _f=mi: (_f(b), i, j)))
        args.append(mask)
    kwargs = {}
    cp = _compiler_params(("parallel", "parallel", "arbitrary"))
    if cp is not None and not interpret:
        kwargs["compiler_params"] = cp
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # lse is one scalar per row: keep it (BH, Lq, 1) so the block's
            # trailing dims (bq, 1) satisfy mosaic's (8, 128)-or-full tiling
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **kwargs,
    )(*args)


# ----------------------------------------------------------------- backward
def _bwd_p(q, k, lse, mask_blk, scale, causal, off, q_start, k_start, bq, bk,
           mask_rows, lk_real, window):
    """Recompute p = exp(s - lse) for one block of the backward sweeps."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    cols = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = cols < lk_real
    if causal:
        rows = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        keep = jnp.logical_and(keep, rows + off >= cols)
        if window:
            keep = jnp.logical_and(keep, cols > rows + off - window)
    s = jnp.where(keep, s, _NEG_INF)
    if mask_blk is not None:
        m = mask_blk.astype(jnp.float32)
        if mask_rows == 1:
            m = jnp.broadcast_to(m, (bq, bk))
        s = s + m
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    return jnp.exp(s - lse_safe)          # masked / padded rows -> 0


def _dkv_kernel(*refs, scale, causal, off, bq, bk, nq, g, has_mask,
                mask_rows, lk_real, window):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        mask_ref = None
    iq = pl.program_id(2)   # combined (q block, GQA group member) index
    jk = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q_start = (iq // g) * bq
    k_start = jk * bk
    run = (q_start + bq + off > k_start) if causal else (iq >= 0)
    run = jnp.logical_and(run, k_start < lk_real)
    if window:
        run = jnp.logical_and(run,
                              k_start + bk - 1 > q_start + off - window)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                  # (bq, 1)
        delta = delta_ref[0]
        p = _bwd_p(q, k, lse, None if mask_ref is None else mask_ref[0],
                   scale, causal, off, q_start, k_start, bq, bk,
                   mask_rows, lk_real, window)
        dv_s[...] += lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_s[...] += lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _dq_kernel(*refs, scale, causal, off, bq, bk, nk, has_mask, mask_rows,
               lk_real, window):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_s) = refs
        mask_ref = None
    jk = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q_start = iq * bq
    k_start = jk * bk
    run = (q_start + bq + off > k_start) if causal else (jk >= 0)
    run = jnp.logical_and(run, k_start < lk_real)
    if window:
        run = jnp.logical_and(run,
                              k_start + bk - 1 > q_start + off - window)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        p = _bwd_p(q, k, lse, None if mask_ref is None else mask_ref[0],
                   scale, causal, off, q_start, k_start, bq, bk,
                   mask_rows, lk_real, window)
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_s[...] += lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jk == nk - 1)
    def _emit():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, mask, causal, scale, bq, bk, interpret, H, Hkv,
         mask_meta, lk_real, window=0):
    mask_meta = dict(mask_meta)
    BH, Lq, D = q.shape
    BHkv, Lk, _ = k.shape
    nq, nk = Lq // bq, Lk // bk
    off = mask_meta["off"]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)        # (BH, Lq, 1), same layout as lse
    has_mask = mask is not None
    mask_rows = 0 if not has_mask else mask_meta["rows"]
    kvi = _kv_index(H, Hkv)
    g = H // Hkv

    kw = {}
    cp = _compiler_params(("parallel", "parallel", "arbitrary"))
    if cp is not None and not interpret:
        kw["compiler_params"] = cp

    # --- dK/dV: grid over kv-BH so each kv head accumulates its whole
    # query group sequentially (group size g folded into the iq axis)
    q_spec = pl.BlockSpec(
        (1, bq, D), lambda b, j, i, _g=g, _H=H, _Hkv=Hkv:
        ((b // _Hkv) * _H + (b % _Hkv) * _g + i % _g, i // _g, 0))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec(
        (1, bq, 1), lambda b, j, i, _g=g, _H=H, _Hkv=Hkv:
        ((b // _Hkv) * _H + (b % _Hkv) * _g + i % _g, i // _g, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    args = [q, k, v, do, lse, delta]
    if has_mask:
        mi = _mask_index(mask_meta, H)

        def m_idx(b, j, i, _g=g, _H=H, _Hkv=Hkv, _f=mi):
            bh = (b // _Hkv) * _H + (b % _Hkv) * _g + i % _g
            return (_f(bh), 0 if mask_rows == 1 else i // _g, j)
        in_specs.append(pl.BlockSpec(
            (1, 1, bk) if mask_rows == 1 else (1, bq, bk), m_idx))
        args.append(mask)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          off=off, bq=bq, bk=bk, nq=nq * g, g=g,
                          has_mask=has_mask, mask_rows=mask_rows,
                          lk_real=lk_real, window=window),
        grid=(BHkv, nk, nq * g),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BHkv, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
        **kw,
    )(*args)

    # --- dQ: grid over q-BH
    q_spec2 = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, bk, D),
                            lambda b, i, j, _f=kvi: (_f(b), j, 0))
    row_spec2 = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    args2 = [q, k, v, do, lse, delta]
    if has_mask:
        mi = _mask_index(mask_meta, H)
        if mask_rows == 1:
            in_specs2.append(pl.BlockSpec(
                (1, 1, bk), lambda b, i, j, _f=mi: (_f(b), 0, j)))
        else:
            in_specs2.append(pl.BlockSpec(
                (1, bq, bk), lambda b, i, j, _f=mi: (_f(b), i, j)))
        args2.append(mask)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          off=off, bq=bq, bk=bk, nk=nk,
                          has_mask=has_mask, mask_rows=mask_rows,
                          lk_real=lk_real, window=window),
        grid=(BH, nq, nk),
        in_specs=in_specs2,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dq",
        **kw,
    )(*args2)
    return dq, dk, dv


# -------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10,
                                                    11, 12, 13))
def _flash_core(q, k, v, mask, causal, scale, bq, bk, interpret, H, Hkv,
                mask_meta, lk_real, window):
    o, _ = _fwd(q, k, v, mask, causal, scale, bq, bk, interpret, H, Hkv,
                mask_meta, lk_real, window)
    return o


def _flash_fwd_rule(q, k, v, mask, causal, scale, bq, bk, interpret, H, Hkv,
                    mask_meta, lk_real, window):
    o, lse = _fwd(q, k, v, mask, causal, scale, bq, bk, interpret, H, Hkv,
                  mask_meta, lk_real, window)
    return o, (q, k, v, mask, o, lse)


def _flash_bwd_rule(causal, scale, bq, bk, interpret, H, Hkv, mask_meta,
                    lk_real, window, res, do):
    q, k, v, mask, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, mask, causal, scale, bq, bk,
                      interpret, H, Hkv, mask_meta, lk_real, window)
    # masks are inputs, not trained parameters: zero cotangent
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dmask


_flash_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ----------------------------------------------------------------- wrapper
def _normalize_mask(mask, B, H, Lq, Lk):
    """-> (mask3d or None, meta).  Layouts: (Bm*Hm, mlq, Lk) with the
    batch/head broadcasts recorded in meta and folded into the kernel's
    block index map — a broadcast mask is never materialized per
    batch/head.  bool -> additive f32."""
    if mask is None:
        return None, {"heads": 1, "rows": 0}
    m = mask
    if m.ndim == 2:
        m = m[None, None]
    elif m.ndim == 3:
        m = m[:, None]
    mb, mh, mlq, mlk = m.shape
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, _NEG_INF).astype(jnp.float32)
    else:
        m = m.astype(jnp.float32)
    m3 = m.reshape(mb * mh, mlq, mlk)
    return m3, {"heads": mh, "batch1": mb == 1 and B > 1,
                "rows": 1 if mlq == 1 else mlq}


def flash_attention(q, k, v, mask=None, is_causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False,
                    window=None):
    """Flash attention on (B, L, H, D) arrays; D padded to the lane width,
    seq lens padded to the block grid, GQA via kv-head grouping.
    Returns (B, Lq, H, D) in the input dtype.

    ``window`` (sliding-window attention, Mistral-style): row r attends
    only cols in (r+off-window, r+off].  Requires is_causal; KV blocks
    entirely left of the band are SKIPPED, so compute scales with
    window*Lq instead of Lq*Lk at long context."""
    window = int(window or 0)
    if window and not is_causal:
        raise ValueError("window requires is_causal=True")
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    Hkv = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    if block_q is None or block_k is None:
        tbq, tbk = _default_blocks(D, Lq, Lk)
        block_q = block_q or tbq
        block_k = block_k or tbk
    if block_q % 8 or block_k % 8:
        raise ValueError(
            f"flash_attention block sizes must be multiples of the TPU "
            f"sublane width (8); got block_q={block_q}, block_k={block_k}")
    bq = min(block_q, _pad_to(Lq, 8))
    bk = min(block_k, _pad_to(Lk, 8))
    Lqp, Lkp = _pad_to(Lq, bq), _pad_to(Lk, bk)

    def to_bh(x, h):
        return x.transpose(0, 2, 1, 3).reshape(B * h, x.shape[1], D)

    qb, kb, vb = to_bh(q, H), to_bh(k, Hkv), to_bh(v, Hkv)
    m3, mask_meta = _normalize_mask(mask, B, H, Lq, Lk)
    # bottom-right-aligned causal offset over REAL lengths
    mask_meta["off"] = Lk - Lq
    Dp = _pad_to(D, _LANES)
    if Lqp != Lq or Lkp != Lk or Dp != D:
        qb = jnp.pad(qb, [(0, 0), (0, Lqp - Lq), (0, Dp - D)])
        kb = jnp.pad(kb, [(0, 0), (0, Lkp - Lk), (0, Dp - D)])
        vb = jnp.pad(vb, [(0, 0), (0, Lkp - Lk), (0, Dp - D)])
        if m3 is not None:
            mq_pad = 0 if mask_meta["rows"] == 1 else Lqp - Lq
            m3 = jnp.pad(m3, [(0, 0), (0, mq_pad), (0, Lkp - Lk)])
    if m3 is not None and mask_meta["rows"] != 1:
        mask_meta["rows"] = Lqp
    o = _flash_core(qb, kb, vb, m3, bool(is_causal), scale, bq, bk,
                    bool(interpret), H, Hkv, _hashable(mask_meta), Lk,
                    window)
    if Lqp != Lq or Dp != D:
        o = o[:, :Lq, :D]
    return o.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)


def _hashable(meta):
    return tuple(sorted(meta.items()))


# ------------------------------------------------- ring-attention building
# blocks: raw fwd/bwd kernel entries on (B, L, H, D) arrays WITHOUT the
# custom_vjp — ring attention (distributed/ring_attention.py) composes them
# per KV-ring step and hand-writes the outer vjp, merging per-block
# contributions by log-sum-exp.  The flash backward with a GLOBAL lse is
# exactly the per-block partial gradient (p = exp(s - lse_global) is the
# globally-normalized probability block), so block grads simply sum.

def _geom(q_shape, k_shape):
    B, Lq, H, D = q_shape
    Lk, Hkv = k_shape[1], k_shape[2]
    tbq, tbk = _default_blocks(D, Lq, Lk)
    bq, bk = min(tbq, _pad_to(Lq, 8)), min(tbk, _pad_to(Lk, 8))
    return dict(B=B, Lq=Lq, Lk=Lk, H=H, Hkv=Hkv, D=D, bq=bq, bk=bk,
                Lqp=_pad_to(Lq, bq), Lkp=_pad_to(Lk, bk),
                Dp=_pad_to(D, _LANES))


def _pack_one(x, h, Lp, Dp):
    B, L, _, D = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(B * h, L, D)
    return jnp.pad(x, [(0, 0), (0, Lp - L), (0, Dp - D)])


def flash_block_fwd(q, k, v, is_causal, scale=None, interpret=False):
    """One attention block on (B, L, H, D) shards -> (o (B, Lq, H, D) in
    input dtype, lse (B, H, Lq) f32).  No autodiff rules attached."""
    B, Lq, H, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    g = _geom(q.shape, k.shape)
    qb = _pack_one(q, H, g["Lqp"], g["Dp"])
    kb = _pack_one(k, g["Hkv"], g["Lkp"], g["Dp"])
    vb = _pack_one(v, g["Hkv"], g["Lkp"], g["Dp"])
    meta = {"heads": 1, "rows": 0, "off": g["Lk"] - g["Lq"]}
    o, lse = _fwd(qb, kb, vb, None, bool(is_causal), scale, g["bq"],
                  g["bk"], bool(interpret), H, g["Hkv"], _hashable(meta),
                  g["Lk"])
    o = o[:, :Lq, :D].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    lse = lse[:, :Lq, 0].reshape(B, H, Lq)
    return o, lse


def flash_block_bwd(q, k, v, o, lse, do, is_causal, scale=None,
                    interpret=False):
    """Partial gradients of one ring step given the GLOBAL (o, lse) and do.
    q/o/do: (B, Lq, H, D); k/v: (B, Lk, Hkv, D); lse: (B, H, Lq) f32.
    With the global lse, p = exp(s - lse) is the globally-normalized
    probability block, so these partials simply sum across ring steps
    (delta = rowsum(do*o) is likewise the global correction term).
    Returns (dq, dk, dv) in the input dtypes."""
    B, Lq, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    g = _geom(q.shape, k.shape)
    qb = _pack_one(q, H, g["Lqp"], g["Dp"])
    kb = _pack_one(k, Hkv, g["Lkp"], g["Dp"])
    vb = _pack_one(v, Hkv, g["Lkp"], g["Dp"])
    ob = _pack_one(o, H, g["Lqp"], g["Dp"])
    # padded q rows: do = 0 makes every dk/dv contribution vanish even
    # though their p-row is nonzero (lse pad = 0); dq pad rows are sliced
    dob = _pack_one(do.astype(q.dtype), H, g["Lqp"], g["Dp"])
    lse_b = jnp.pad(lse.reshape(B * H, Lq, 1),
                    [(0, 0), (0, g["Lqp"] - Lq), (0, 0)])
    meta = {"heads": 1, "rows": 0, "off": g["Lk"] - g["Lq"]}
    dq, dk, dv = _bwd(qb, kb, vb, ob, lse_b, dob, None, bool(is_causal),
                      scale, g["bq"], g["bk"], bool(interpret), H, Hkv,
                      _hashable(meta), g["Lk"])
    dq = dq[:, :Lq, :D].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    dk = dk[:, :Lk, :D].reshape(B, Hkv, Lk, D).transpose(0, 2, 1, 3)
    dv = dv[:, :Lk, :D].reshape(B, Hkv, Lk, D).transpose(0, 2, 1, 3)
    return dq, dk, dv


def supports(q_shape, k_shape, mask, dtype, v_shape=None, is_causal=False,
             shards=(1, 1)):
    """Shape/dtype gate for the pallas path; anything else → XLA sdpa.
    Block sizes are internal now (tuned table / padding) so they are no
    longer part of the gate; flash_attention validates explicit ones.

    `shards` = (dp, mp): how many batch and head shards the fleet mesh
    cuts the call into (the kernel then runs per shard inside a
    shard_map — Mosaic kernels cannot be partitioned automatically);
    None = the mesh has a live axis the kernel cannot be split over."""
    if shards is None:
        return False
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False  # float16: Mosaic "Invalid vector type for load"
    B, Lq, H, D = q_shape
    Lk = k_shape[1]
    Hkv = k_shape[2]
    if Hkv == 0 or H % Hkv:  # GQA needs an integer group size
        return False
    dp, mp = shards
    if B % dp or Hkv % mp:   # whole rows and whole GQA groups per shard
        return False
    if (dp, mp) != (1, 1) and mask is not None and \
            len(getattr(mask, "shape", ())) != 4:
        return False  # only [mb, mh, Lq, Lk] masks have a shard rule
    if is_causal and Lq > Lk:  # fully-masked rows: XLA gives NaN, kernel
        return False           # gives 0 — fall back to keep numerics equal
    if k_shape[3] != D:
        return False
    if v_shape is not None and tuple(v_shape) != tuple(k_shape):
        return False  # e.g. MLA-style distinct value head_dim → XLA path
    if mask is not None:
        ms = getattr(mask, "shape", None)
        md = getattr(mask, "dtype", None)
        if ms is None or len(ms) not in (2, 3, 4):
            return False
        if md != jnp.bool_ and md not in _MASK_DTYPES:
            return False
        if len(ms) == 2:
            ms = (1, 1) + tuple(ms)
        elif len(ms) == 3:
            ms = (ms[0], 1, ms[1], ms[2])
        mb, mh, mlq, mlk = ms
        if mb not in (1, B) or mh not in (1, H):
            return False
        if mlq not in (1, Lq) or mlk != Lk:
            return False
        if is_causal and mlq == 1 and Lq != Lk:
            # row-broadcast + bottom-right causal offset interplay is
            # only exercised for the square/self-attn case; play safe
            return False
    if Lq < 1 or Lk < 1:
        return False
    return True
