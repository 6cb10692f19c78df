"""Neural-net kernels (reference: paddle/phi/kernels/{conv,pool,norm,...}).

All shapes follow the reference's conventions: conv/pool are NCHW with OIHW
weights; attention is (batch, seq, heads, head_dim).  Everything lowers to
lax/jnp so XLA maps convs+matmuls onto the MXU; `sdpa` is the XLA fallback
that ops/pallas/flash_attention.py overrides on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .dispatch import register


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _conv_padding(padding, ndim):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * ndim
    padding = list(padding)
    if len(padding) == ndim:
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * ndim:
        return [(int(padding[2 * i]), int(padding[2 * i + 1]))
                for i in range(ndim)]
    raise ValueError(f"bad padding {padding}")


@register("conv2d", amp="allow")
def conv2d_k(x, w, stride=1, padding=0, dilation=1, groups=1,
             data_format="NCHW"):
    if data_format == "NHWC":
        dn = ("NHWC", "OIHW", "NHWC")
    else:
        dn = ("NCHW", "OIHW", "NCHW")
    return lax.conv_general_dilated(
        x, w, window_strides=_pair(stride),
        padding=_conv_padding(padding, 2),
        rhs_dilation=_pair(dilation),
        dimension_numbers=dn, feature_group_count=groups)


@register("s2d_stem_conv", amp="allow")
def s2d_stem_conv_k(x, w):
    """7x7/stride-2/pad-3 stem conv computed as space-to-depth(2) + 4x4
    stride-1 conv — numerically identical, but the MXU sees 12 input
    channels at 112x112 instead of 3 at 224x224 (the MLPerf ResNet TPU
    trick: a 3-channel contraction uses ~2% of the 128 MXU lanes).

    x [b, c, H, W] (H, W even); w [o, c, 7, 7].
    """
    b, c, H, W = x.shape
    o = w.shape[0]
    z = x.reshape(b, c, H // 2, 2, W // 2, 2)
    z = z.transpose(0, 1, 3, 5, 2, 4).reshape(b, c * 4, H // 2, W // 2)
    # pad the kernel top-left to 8x8, then split each spatial dim into
    # (tap, parity) matching the space-to-depth channel packing
    w8 = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))
    w4 = w8.reshape(o, c, 4, 2, 4, 2)
    w4 = w4.transpose(0, 1, 3, 5, 2, 4).reshape(o, c * 4, 4, 4)
    return lax.conv_general_dilated(
        z, w4, window_strides=(1, 1), padding=((2, 1), (2, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


@register("conv1d", amp="allow")
def conv1d_k(x, w, stride=1, padding=0, dilation=1, groups=1):
    s = (int(stride),) if isinstance(stride, int) else tuple(stride)
    d = (int(dilation),) if isinstance(dilation, int) else tuple(dilation)
    return lax.conv_general_dilated(
        x, w, window_strides=s, padding=_conv_padding(padding, 1),
        rhs_dilation=d, dimension_numbers=("NCH", "OIH", "NCH"),
        feature_group_count=groups)


@register("conv3d", amp="allow")
def conv3d_k(x, w, stride=1, padding=0, dilation=1, groups=1):
    def _tri(v):
        return (int(v),) * 3 if isinstance(v, int) else tuple(v)
    return lax.conv_general_dilated(
        x, w, window_strides=_tri(stride), padding=_conv_padding(padding, 3),
        rhs_dilation=_tri(dilation),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=groups)


@register("conv2d_transpose", amp="allow")
def conv2d_transpose_k(x, w, stride=1, padding=0, output_padding=0,
                       dilation=1, groups=1):
    # weight layout IOHW (paddle conv2d_transpose), flip spatial dims
    s = _pair(stride)
    p = _conv_padding(padding, 2)
    if isinstance(p, str):
        raise ValueError("string padding unsupported for transpose conv")
    k = w.shape[2:]
    op = _pair(output_padding)
    d = _pair(dilation)
    pads = [
        (d[i] * (k[i] - 1) - p[i][0],
         d[i] * (k[i] - 1) - p[i][1] + op[i])
        for i in range(2)
    ]
    w_t = jnp.flip(w, axis=(2, 3)).swapaxes(0, 1)  # IOHW→OIHW flipped
    if groups > 1:
        # grouped transpose: block-diagonal over channel groups
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(w, groups, axis=0)
        outs = [conv2d_transpose_k(xi, wi, stride, padding, output_padding,
                                   dilation, 1) for xi, wi in zip(xs, ws)]
        return jnp.concatenate(outs, axis=1)
    return lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1), padding=pads,
        lhs_dilation=s, rhs_dilation=_pair(dilation),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _ceil_extra(size, k, s, p):
    """Extra high-side padding so reduce_window matches ceil_mode output."""
    eff = size + p[0] + p[1]
    out_floor = (eff - k) // s + 1
    out_ceil = -(-(eff - k) // s) + 1
    return (out_ceil - out_floor) * s


@register("max_pool2d")
def max_pool2d_k(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    win, strides, pads, _, _, _ = _pool2d_geom(x, kernel_size, stride,
                                               padding, ceil_mode, False)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
        jnp.iinfo(x.dtype).min
    return lax.reduce_window(x, init, lax.max, win, strides, pads)


@register("max_pool2d_index")
def max_pool2d_index_k(x, kernel_size, stride=None, padding=0,
                       ceil_mode=False):
    """Argmax mask for max_pool2d: flat index into each (H, W) input map,
    matching the reference's max_pool2d(..., return_mask=True) second output
    (python/paddle/nn/functional/pooling.py)."""
    _, _, _, k, p, s = _pool2d_geom(x, kernel_size, stride, padding,
                                    ceil_mode, False)
    H, W = x.shape[2], x.shape[3]
    # -inf (not finfo.min) so padding never beats a real -inf input element,
    # matching max_pool2d_k's reduce_window init value
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    xp = jnp.pad(x, [(0, 0), (0, 0)] + list(p), constant_values=neg)
    # (N, C*kh*kw, Ho, Wo) patches, VALID since we padded by hand
    patches = lax.conv_general_dilated_patches(
        xp, filter_shape=k, window_strides=s, padding="VALID")
    N, _, Ho, Wo = patches.shape
    C = x.shape[1]
    patches = patches.reshape(N, C, k[0] * k[1], Ho, Wo)
    local = jnp.argmax(patches, axis=2)          # (N, C, Ho, Wo)
    lh, lw = local // k[1], local % k[1]
    oh = jnp.arange(Ho).reshape(1, 1, Ho, 1)
    ow = jnp.arange(Wo).reshape(1, 1, 1, Wo)
    gh = oh * s[0] - p[0][0] + lh
    gw = ow * s[1] - p[1][0] + lw
    return (gh * W + gw).astype(jnp.int32)


@register("avg_pool2d")
def avg_pool2d_k(x, kernel_size, stride=None, padding=0, ceil_mode=False,
                 exclusive=True):
    win, strides, pads, k, p, _ = _pool2d_geom(x, kernel_size, stride,
                                               padding, ceil_mode, False)
    summed = lax.reduce_window(x, 0.0, lax.add, win, strides, pads)
    if exclusive and any(pi != (0, 0) for pi in p):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, win, strides, pads)
        return summed / jnp.maximum(counts, 1.0)
    return summed / (k[0] * k[1])


@register("adaptive_avg_pool2d")
def adaptive_avg_pool2d_k(x, output_size):
    oh, ow = _pair(output_size)
    _, _, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        x4 = x.reshape(x.shape[0], x.shape[1], oh, h // oh, ow, w // ow)
        return x4.mean(axis=(3, 5))
    rows = []
    for i in range(oh):
        h0, h1 = (i * h) // oh, -(-((i + 1) * h) // oh)
        cols = []
        for j in range(ow):
            w0, w1 = (j * w) // ow, -(-((j + 1) * w) // ow)
            cols.append(x[:, :, h0:h1, w0:w1].mean(axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


@register("adaptive_max_pool2d")
def adaptive_max_pool2d_k(x, output_size):
    oh, ow = _pair(output_size)
    _, _, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        x4 = x.reshape(x.shape[0], x.shape[1], oh, h // oh, ow, w // ow)
        return x4.max(axis=(3, 5))
    raise NotImplementedError("adaptive_max_pool2d: non-divisible sizes")


@register("interpolate")
def interpolate_k(x, size=None, scale_factor=None, mode="nearest",
                  align_corners=False):
    n, c, h, w = x.shape
    if size is None:
        sf = _pair(scale_factor) if not isinstance(scale_factor, float) \
            else (scale_factor, scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    size = _pair(size)
    if align_corners and mode in ("bilinear", "linear") and \
            size[0] > 1 and size[1] > 1:
        # corner-aligned sampling grid (jax.image.resize is half-pixel only)
        oh, ow = size
        ys = jnp.linspace(0.0, h - 1.0, oh)
        xs = jnp.linspace(0.0, w - 1.0, ow)
        y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 2)
        x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 2)
        wy = (ys - y0)[None, None, :, None]
        wx = (xs - x0)[None, None, None, :]
        g = x[:, :, y0][:, :, :, x0]
        g01 = x[:, :, y0][:, :, :, x0 + 1]
        g10 = x[:, :, y0 + 1][:, :, :, x0]
        g11 = x[:, :, y0 + 1][:, :, :, x0 + 1]
        top = g * (1 - wx) + g01 * wx
        bot = g10 * (1 - wx) + g11 * wx
        return top * (1 - wy) + bot * wy
    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "bicubic": "cubic"}[mode]
    return jax.image.resize(x, (n, c) + size, method=method)


@register("pixel_shuffle")
def pixel_shuffle_k(x, upscale_factor):
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(n, c // (r * r), h * r, w * r)


# ----------------------------------------------------------------- norms
@register("layer_norm", amp="deny")
def layer_norm_k(x, weight, bias, normalized_ndim=1, eps=1e-5):
    axes = tuple(range(x.ndim - normalized_ndim, x.ndim))
    mean = x.mean(axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@register("rms_norm", amp="deny")
def rms_norm_k(x, weight, eps=1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = (xf * lax.rsqrt(ms + eps)).astype(dtype)
    return out * weight if weight is not None else out


@register("group_norm", amp="deny")
def group_norm_k(x, weight, bias, num_groups, eps=1e-5):
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    xg = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, xg.ndim))
    mean = xg.mean(axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    shape = (1, c) + (1,) * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@register("batch_norm_infer", amp="deny")
def batch_norm_infer_k(x, weight, bias, mean, var, eps=1e-5, axis=1):
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    out = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@register("batch_norm_train", amp="deny")
def batch_norm_train_k(x, weight, bias, eps=1e-5, axis=1):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    mean = x.mean(axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    out = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, mean, var


# --------------------------------------------------------------- embedding
@register("embedding")
def embedding_k(w, ids, padding_idx=None):
    if padding_idx is not None:
        # the padding row contributes no gradient (reference semantics)
        w = w.at[padding_idx].set(lax.stop_gradient(w[padding_idx]))
    return jnp.take(w, ids, axis=0)


# --------------------------------------------------------------- attention
@register("sdpa", amp="allow")
def sdpa_k(q, k, v, mask=None, is_causal=False, scale=None,
           sliding_window=None, _mask_needs_grad=False):
    """Scaled dot-product attention, (B, L, H, D) layout like the reference's
    nn.functional.scaled_dot_product_attention. Softmax in fp32.
    GQA: fewer kv heads are repeat_interleave-broadcast up to q heads (the
    pallas override handles grouping natively, without the repeat).
    `_mask_needs_grad` is consumed by the pallas override (forces this XLA
    path, which differentiates through `scores + mask`); ignored here."""
    d = q.shape[-1]
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * scale
    scores = scores.astype(jnp.float32)
    if is_causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)
        if sliding_window:
            # banded causal (Mistral SWA): col in (r+off-W, r+off]
            cm &= jnp.triu(jnp.ones((lq, lk), bool),
                           lk - lq - int(sliding_window) + 1)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -jnp.inf)
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


# --------------------------------------------- paged KV cache (serving)
@register("paged_write")
def paged_write_k(pool, val, tables, pos, limit, block_size=16):
    """Scatter `val` [b, s, H, D] into the paged KV pool [N, bs, H, D]
    at per-row sequence positions pos[b]..pos[b]+s-1, routed through each
    row's block table (position p lands in block tables[b, p // bs] at
    slot p % bs).  Positions >= limit[b] are DROPPED — that one guard
    covers bucket padding (prefill chunks padded up a shape bucket) and
    dead decode slots (limit 0 writes nothing), so the pool only ever
    holds tokens the scheduler accounted for."""
    bs = int(block_size)
    s = val.shape[1]
    positions = (pos.astype(jnp.int32)[:, None]
                 + jnp.arange(s, dtype=jnp.int32)[None, :])      # [b, s]
    blk = jnp.take_along_axis(
        tables.astype(jnp.int32),
        jnp.clip(positions // bs, 0, tables.shape[1] - 1), axis=1)
    off = positions % bs
    # out-of-range block id -> scatter mode="drop" discards the write
    blk = jnp.where(positions < limit.astype(jnp.int32)[:, None],
                    blk, pool.shape[0])
    return pool.at[blk, off].set(val.astype(pool.dtype), mode="drop")


@register("paged_attention", amp="allow")
def paged_attention_k(q, k_pool, v_pool, tables, pos, scale=None,
                      window=None):
    """Decode/prefill attention over the paged KV pool — the jnp `take`
    reference implementation (the pallas TPU kernels in
    ops/pallas/paged_attention.py, one for a decode step and one for a
    prefill chunk, override this at import).

    Gathers each row's blocks into a contiguous [b, M*bs, Hkv, D] window
    and runs the exact `sdpa_k` math under the paged length mask
    (q row i of a request at context offset pos attends absolute
    positions <= pos + i), so CPU tier-1 numerics are bit-identical to
    the dense-cache path.

    With `window` (a host number) row i sees the positions in
    ``(pos + i - window, pos + i]`` alone, and only the table columns a
    chunk can see are gathered: ``cdiv(window - 1 + s, bs) + 1`` of them
    from the block that holds position ``pos - (window - 1)``.  The
    columns before it are never read, so their entries may hold any id
    (the serving pool hands those blocks back)."""
    b, s = q.shape[0], q.shape[1]
    bs = k_pool.shape[1]
    m = tables.shape[1]
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    first = 0
    if window is not None:
        window = int(window)
        n = min(m, -(-(window - 1 + s) // bs) + 1)
        first = (jnp.maximum(pos - (window - 1), 0) // bs)[:, None]  # [b, 1]
        # a column past the table repeats its last: those positions lie
        # past every row's own and are masked
        tables = jnp.take_along_axis(
            tables, jnp.minimum(first + jnp.arange(n, dtype=jnp.int32),
                                m - 1), axis=1)
        m = n
    flat = tables.reshape(-1)
    K = jnp.take(k_pool, flat, axis=0).reshape(
        (b, m * bs) + k_pool.shape[2:])
    V = jnp.take(v_pool, flat, axis=0).reshape(
        (b, m * bs) + v_pool.shape[2:])
    cols = jnp.arange(m * bs, dtype=jnp.int32)[None, None, :]
    rows = (pos[:, None, None]
            + jnp.arange(s, dtype=jnp.int32)[None, :, None])
    if window is None:
        mask = cols <= rows
    else:
        cols = cols + first[:, :, None] * bs
        mask = (cols <= rows) & (cols > rows - window)
    return sdpa_k(q, K, V, mask=mask[:, None, :, :], scale=scale)


def paged_gather_k(pool, tables):
    """A row's blocks as one contiguous window: pool [N, bs, ...],
    tables [b, M] -> [b, M * bs, ...] (what the latent gather reference
    attends)."""
    b, m = tables.shape
    rows = jnp.take(pool, tables.astype(jnp.int32).reshape(-1), axis=0)
    return rows.reshape((b, m * pool.shape[1]) + pool.shape[2:])


def paged_visible(s, length, pos):
    """[b, s, length] bool: query row i of a request at context offset
    pos[b] sees absolute positions <= pos[b] + i of its gathered window."""
    cols = jnp.arange(length, dtype=jnp.int32)[None, None, :]
    return cols <= (pos.astype(jnp.int32)[:, None, None]
                    + jnp.arange(s, dtype=jnp.int32)[None, :, None])


@register("latent_paged_attention", amp="allow")
def latent_paged_attention_k(q, pool, tables, pos, value_dim, scale=None):
    """Attention of ABSORBED queries over a latent paged pool -- the jnp
    gather reference (ops/pallas/latent_paged_attention.py overrides it
    on TPU: a decode step's kernel and a prefill chunk's).

    One cached row serves every head as key (all of its `W` columns) and
    as value (its first `value_dim` columns): q [b, s, H, W], pool
    [N, bs, W], tables [b, M], pos [b] -> [b, s, H, value_dim].  Query
    row i of a request at context offset pos attends positions
    <= pos + i; the softmax is float32, as `sdpa_k`'s."""
    b, s = q.shape[0], q.shape[1]
    rows = paged_gather_k(pool, tables)                       # [b, L, W]
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    scores = (jnp.einsum("bshw,blw->bhsl", q, rows) * scale).astype(
        jnp.float32)
    seen = paged_visible(s, rows.shape[1], pos)
    scores = jnp.where(seen[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhsl,blv->bshv", probs, rows[..., :value_dim])


def indexer_scores(q_idx, w_idx, keys):
    """A lightning indexer's scores, float32: q_idx [b, s, h, W], w_idx
    [b, s, h], keys [b, L, W] (one key head) -> [b, s, L] with
    ``I[t, l] = sum_j w[t, j] * relu(q[t, j] . k[l])``."""
    f32 = jnp.float32
    dots = jnp.einsum("bshw,blw->bshl", q_idx.astype(f32), keys.astype(f32))
    return jnp.einsum("bshl,bsh->bsl", jnp.maximum(dots, 0.0),
                      w_idx.astype(f32))


def sparse_select(scores, topk):
    """[b, s, L] bool: the `topk` positions of the largest scores in each
    query's row (`lax.top_k`'s, the lower position first on a tie); a
    position a query cannot see carries -inf, and is picked only where
    the row holds fewer than `topk` it can see (the caller masks those)."""
    b, s, length = scores.shape
    top = jax.lax.top_k(scores, min(int(topk), length))[1]
    return jnp.zeros(scores.shape, bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        top].set(True)


@register("sparse_paged_attention", amp="allow")
def sparse_paged_attention_k(q, k_pool, v_pool, ik_pool, q_idx, w_idx,
                             tables, pos, topk, scale=None):
    """Attention of each query over the `topk` cached positions a learned
    indexer picks (DeepSeek-V3.2's sparse attention over a GQA pool) --
    the jnp gather reference (ops/pallas/sparse_attention.py overrides
    it on TPU).

    q [b, s, H, D] over K/V pools [N, bs, Hkv, D]; the indexer's queries
    q_idx [b, s, h, W] and weights w_idx [b, s, h] over its key pool
    ik_pool [N, bs, W]; tables [b, M]; query row i of a request at
    context offset pos sees positions <= pos + i, and attends the `topk`
    of them with the largest indexer scores (`sparse_select`).  Every
    head of a query shares its selection."""
    s = q.shape[1]
    keys = paged_gather_k(ik_pool, tables)                    # [b, L, W]
    seen = paged_visible(s, keys.shape[1], pos)
    scores = jnp.where(seen, indexer_scores(q_idx, w_idx, keys), -jnp.inf)
    mask = seen & sparse_select(scores, topk)
    K = paged_gather_k(k_pool, tables)
    V = paged_gather_k(v_pool, tables)
    return sdpa_k(q, K, V, mask=mask[:, None], scale=scale)


# ------------------------------------------------- grouped products (MoE)
@register("grouped_matmul")
def grouped_matmul_k(rows, weights, group_sizes):
    """Each group's contiguous rows against its own matrix: rows [m, k]
    sorted by group, weights [g, k, n], group_sizes [g] int32 (their sum
    is m) -> [m, n].  XLA's own ragged product; ops/pallas/ overrides it
    with a tiled grouped-matmul kernel on TPU."""
    return jax.lax.ragged_dot(rows, weights, group_sizes)


# ------------------------------------- gated delta rule (KDA token mixer)
_KDA_CHUNK = 64


def _kda_inverse(n):
    """(I + n)^-1 of a strictly lower triangular [..., C, C] as products:
    with m = -n nilpotent, (I + m)(I + m^2)(I + m^4)... holds every power
    below C after log2(C) factors."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)
    power = -n
    inv = eye + power
    for _ in range(max(0, (c - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision="highest")
        inv = inv + jnp.matmul(inv, power, precision="highest")
    return inv


@register("kda_chunk")
def kda_chunk_k(q, k, v, g, beta, state, n_valid=None, chunk=_KDA_CHUNK):
    """Kimi Delta Attention (arXiv:2510.26692) over a run of positions in
    its chunkwise form.  Per head, with ``a_t = exp(g_t)`` per channel:

        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    q, k [b, T, H, dk] (normalised and scaled by the caller), v
    [b, T, H, dv], g [b, T, H, dk] (log decay, <= 0), beta [b, T, H],
    state [b, H, dk, dv] float32 -> (o [b, T, H, dv] float32, the state
    after the run).  Positions at or past ``n_valid[b]`` are inert
    (a = 1, beta = 0): the state does not move past them.

    Chunks of `chunk` positions: inside one, the pseudo-values
    ``u_t = beta_t (v_t - S~_t^T k_t)`` solve the unit lower triangular
    system ``(I + Diag(beta) A) U = Diag(beta)(V - (K . Gamma) S_0)`` with
    ``A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])`` (i < t, G the
    running sum of g), solved as products (`_kda_inverse`); the state
    moves once a chunk.  Every exponent is a difference G_t - G_i with
    i <= t, never a bare exp(-G): per-channel decays reach exp(-500)
    inside a chunk.  All in float32 at `highest` matmul precision."""
    f32 = jnp.float32
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if n_valid is not None:
        real = jnp.arange(t, dtype=jnp.int32)[None, :] \
            < n_valid.astype(jnp.int32)[:, None]
        g = jnp.where(real[:, :, None, None], g, 0.0)
        beta = jnp.where(real[:, :, None], beta, 0.0)
    c = min(int(chunk), t)
    pad = -t % c
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // c

    def chunks(a):      # [b, n * c, H, ...] -> [n, b, H, c, ...]
        a = a.reshape((b, n, c, h) + a.shape[3:])
        return jnp.moveaxis(jnp.swapaxes(a, 2, 3), 1, 0)

    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    mm = functools.partial(jnp.matmul, precision="highest")

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs             # [b, H, c, d]; bc [b, H, c]
        gsum = jnp.cumsum(gc, axis=2)
        decay = jnp.exp(jnp.minimum(
            gsum[:, :, :, None, :] - gsum[:, :, None, :, :], 0.0))
        kk = jnp.sum(kc[:, :, :, None, :] * kc[:, :, None, :, :] * decay, -1)
        qk = jnp.sum(qc[:, :, :, None, :] * kc[:, :, None, :, :] * decay, -1)
        inv = _kda_inverse(jnp.where(strict, bc[..., None] * kk, 0.0))
        grow = jnp.exp(gsum)
        u = mm(inv, bc[..., None] * (vc - mm(kc * grow, s0)))
        o = mm(qc * grow, s0) + mm(
            jnp.where(strict | jnp.eye(c, dtype=bool), qk, 0.0), u)
        last = gsum[:, :, -1:, :]
        s1 = jnp.swapaxes(jnp.exp(last), 2, 3) * s0 + mm(
            jnp.swapaxes(kc * jnp.exp(last - gsum), 2, 3), u)
        return s1, o

    state, o = jax.lax.scan(one, state.astype(f32),
                            tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, dv)
    return o[:, :t], state


@register("kda_step")
def kda_step_k(q, k, v, g, beta, state, slots, live):
    """One position of the recurrence of `kda_chunk` for every row of a
    decode program, over the pool of states, addressed by slot: q, k, g
    [R, H, dk], v [R, H, dv], beta [R, H], state [S, H, dk, dv] float32,
    slots [R] int32, live [R] bool -> (o [R, H, dv] float32, the pool).
    A row that is not live reads its slot and writes nothing.  The XLA
    form gathers, updates and scatters (ops/pallas/kda.py updates the
    pool in place on TPU)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s0 = state[slots] * jnp.exp(g)[..., None]
    delta = beta[..., None] * (v - jnp.sum(k[..., None] * s0, axis=-2))
    s1 = s0 + k[..., None] * delta[..., None, :]
    o = jnp.sum(q[..., None] * s1, axis=-2)
    at = jnp.where(live, slots.astype(jnp.int32), state.shape[0])
    return o, state.at[at].set(s1.astype(state.dtype), mode="drop")


# ------------------------------------------------------------------ losses
@register("softmax_ce", amp="deny")
def softmax_ce_k(logits, label, soft_label=False, ignore_index=-100,
                 label_smoothing=0.0, axis=-1):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=axis)
    n_cls = logits.shape[axis]
    if soft_label:
        tgt = label
    else:
        tgt = jax.nn.one_hot(label, n_cls, axis=axis, dtype=logp.dtype)
    if label_smoothing > 0.0:
        tgt = tgt * (1.0 - label_smoothing) + label_smoothing / n_cls
    loss = -(tgt * logp).sum(axis=axis)
    if not soft_label:
        valid = (label != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
    return loss


@register("bce_with_logits", amp="deny")
def bce_with_logits_k(logit, label, pos_weight=None):
    logit = logit.astype(jnp.float32)
    label = label.astype(jnp.float32)
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_weight = (pos_weight - 1.0) * label + 1.0
        loss = (1.0 - label) * logit + log_weight * (
            jnp.log(jnp.exp(-max_val) + jnp.exp(-logit - max_val)) + max_val)
    else:
        loss = (1.0 - label) * logit + max_val + jnp.log(
            jnp.exp(-max_val) + jnp.exp(-logit - max_val))
    return loss


@register("ctc_loss", amp="deny")
def ctc_loss_k(logits, labels, input_lengths, label_lengths, blank=0):
    """CTC negative log-likelihood per batch element (reference:
    paddle.nn.functional.ctc_loss over warpctc).

    logits [T, B, C] (UNnormalized; log_softmax applied here), labels
    [B, S] padded with anything, input_lengths [B], label_lengths [B].
    Standard alpha recursion on the blank-extended label sequence in the
    log semiring, as one lax.scan over time — static shapes, so the whole
    loss (and its gradient, via autodiff) is a single XLA program.
    """
    T, B, C = logits.shape
    S = labels.shape[1]
    L = 2 * S + 1
    neg_inf = -1e30
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    labels = labels.astype(jnp.int32)
    ext = jnp.full((B, L), blank, jnp.int32).at[:, 1::2].set(labels)
    # the s-2 diagonal skip is allowed when ext[s] is a label differing
    # from ext[s-2]
    skip_ok = jnp.concatenate(
        [jnp.zeros((B, 2), bool),
         (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2])], axis=1)
    batch_idx = jnp.arange(B)[:, None]
    emit = lp[:, batch_idx, ext]                     # [T, B, L]

    alpha = jnp.full((B, L), neg_inf)
    alpha = alpha.at[:, 0].set(emit[0, :, 0])
    alpha = alpha.at[:, 1].set(jnp.where(labels.shape[1] > 0,
                                         emit[0, :, 1], neg_inf))

    def shift(a, n):
        return jnp.concatenate(
            [jnp.full((B, n), neg_inf), a[:, :-n]], axis=1) if n else a

    def body(alpha, t):
        stay = alpha
        s1 = shift(alpha, 1)
        s2 = jnp.where(skip_ok, shift(alpha, 2), neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(stay, s1), s2)
        new = merged + emit[t]
        # frames beyond a sequence's input length leave alpha unchanged
        active = (t < input_lengths.astype(jnp.int32))[:, None]
        return jnp.where(active, new, alpha), None

    alpha, _ = jax.lax.scan(body, alpha, jnp.arange(1, T))
    ll = labels_len = label_lengths.astype(jnp.int32)
    last = alpha[batch_idx[:, 0], 2 * ll]            # ends on final blank
    prev = jnp.where(ll > 0,
                     alpha[batch_idx[:, 0],
                           jnp.maximum(2 * ll - 1, 0)], neg_inf)
    return -jnp.logaddexp(last, prev)


@register("fold", amp="keep")
def fold_k(x, output_sizes, kernel_sizes, strides=1, paddings=0,
           dilations=1):
    """col2im — inverse of unfold (reference: paddle.nn.functional.fold).
    x [N, C*kh*kw, L] -> [N, C, H, W] with overlapping patches summed."""
    H, W = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    N = x.shape[0]
    C = x.shape[1] // (kh * kw)
    oh = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    ow = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    cols = x.reshape(N, C, kh, kw, oh, ow)
    out = jnp.zeros((N, C, H + 2 * ph + dh * kh, W + 2 * pw + dw * kw),
                    x.dtype)
    for i in range(kh):          # static small loops: XLA fuses the adds
        for j in range(kw):
            out = out.at[:, :,
                         i * dh: i * dh + sh * oh: sh,
                         j * dw: j * dw + sw * ow: sw].add(cols[:, :, i, j])
    return out[:, :, ph:ph + H, pw:pw + W]


@register("max_unpool2d", amp="keep")
def max_unpool2d_k(x, indices, out_h, out_w):
    """Scatter pooled values back to their argmax positions (reference:
    paddle.nn.functional.max_unpool2d; indices are flat (H*W) positions
    from max_pool2d(..., return_mask=True))."""
    N, C, oh, ow = x.shape
    flat = jnp.zeros((N, C, out_h * out_w), x.dtype)
    b = jnp.arange(N)[:, None, None, None]
    c = jnp.arange(C)[None, :, None, None]
    # .set, not .add: with overlapping pool windows (stride < kernel) one
    # input element can be the argmax of two windows; both scatters carry
    # the same value and must not double it
    flat = flat.at[b, c, indices.astype(jnp.int32)].set(x)
    return flat.reshape(N, C, out_h, out_w)


# ---------------------------------------------- round-3 API-audit kernels
def _tri(v):
    return (int(v),) * 3 if isinstance(v, int) else tuple(v)


@register("max_pool3d")
def max_pool3d_k(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    k = _tri(kernel_size)
    s = _tri(stride if stride is not None else kernel_size)
    p = _conv_padding(padding, 3)
    if ceil_mode:
        p = [(p[i][0], p[i][1] + _ceil_extra(x.shape[2 + i], k[i], s[i],
                                             p[i])) for i in range(3)]
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
        jnp.iinfo(x.dtype).min
    return lax.reduce_window(
        x, init, lax.max, (1, 1) + k, (1, 1) + s,
        [(0, 0), (0, 0)] + list(p))


@register("avg_pool3d")
def avg_pool3d_k(x, kernel_size, stride=None, padding=0, ceil_mode=False,
                 exclusive=True):
    k = _tri(kernel_size)
    s = _tri(stride if stride is not None else kernel_size)
    p = _conv_padding(padding, 3)
    if ceil_mode:
        p = [(p[i][0], p[i][1] + _ceil_extra(x.shape[2 + i], k[i], s[i],
                                             p[i])) for i in range(3)]
    win, strides = (1, 1) + k, (1, 1) + s
    pads = [(0, 0), (0, 0)] + list(p)
    summed = lax.reduce_window(x, 0.0, lax.add, win, strides, pads)
    if exclusive and any(pi != (0, 0) for pi in p):
        counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, win,
                                   strides, pads)
        return summed / jnp.maximum(counts, 1.0)
    return summed / (k[0] * k[1] * k[2])


@register("conv3d_transpose", amp="allow")
def conv3d_transpose_k(x, w, stride=1, padding=0, output_padding=0,
                       dilation=1, groups=1):
    s = _tri(stride)
    p = _conv_padding(padding, 3)
    if isinstance(p, str):
        raise ValueError("string padding unsupported for transpose conv")
    k = w.shape[2:]
    op = _tri(output_padding)
    d = _tri(dilation)
    pads = [(d[i] * (k[i] - 1) - p[i][0],
             d[i] * (k[i] - 1) - p[i][1] + op[i]) for i in range(3)]
    if groups > 1:
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(w, groups, axis=0)
        outs = [conv3d_transpose_k(xi, wi, stride, padding, output_padding,
                                   dilation, 1) for xi, wi in zip(xs, ws)]
        return jnp.concatenate(outs, axis=1)
    w_t = jnp.flip(w, axis=(2, 3, 4)).swapaxes(0, 1)
    return lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1, 1), padding=pads,
        lhs_dilation=s, rhs_dilation=d,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))


@register("instance_norm_op")
def instance_norm_k(x, weight=None, bias=None, eps=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = x.mean(axis=axes, keepdims=True)
    var = x.var(axis=axes, keepdims=True)
    out = (x - mean) / jnp.sqrt(var + eps)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@register("local_response_norm_op")
def local_response_norm_k(x, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    pads = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2)
    acc = lax.reduce_window(sq, 0.0, lax.add,
                            (1, size) + (1,) * (x.ndim - 2),
                            (1,) * x.ndim, pads)
    return x / jnp.power(k + alpha * acc / size, beta)


@register("temporal_shift_op")
def temporal_shift_k(x, seg_num, shift_ratio=0.25):
    # (N*T, C, H, W) -> shift 1/4 channels backward, 1/4 forward in time
    nt, c, h, w = x.shape
    n = nt // seg_num
    x5 = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    back = jnp.concatenate([x5[:, 1:, :fold], jnp.zeros_like(
        x5[:, :1, :fold])], axis=1)
    fwd = jnp.concatenate([jnp.zeros_like(x5[:, :1, fold:2 * fold]),
                           x5[:, :-1, fold:2 * fold]], axis=1)
    rest = x5[:, :, 2 * fold:]
    return jnp.concatenate([back, fwd, rest], axis=2).reshape(nt, c, h, w)


@register("gather_tree_op")
def gather_tree_k(ids, parents):
    """(T, B, beam) beam-search ancestry walk (reference: fluid gather_tree
    → paddle.nn.functional.gather_tree)."""
    T = ids.shape[0]

    def body(carry, xs):
        beam_idx = carry                     # (B, beam)
        step_ids, step_parents = xs
        out = jnp.take_along_axis(step_ids, beam_idx, axis=1)
        nxt = jnp.take_along_axis(step_parents, beam_idx, axis=1)
        return nxt, out

    init = jnp.broadcast_to(jnp.arange(ids.shape[2])[None, :],
                            ids.shape[1:])
    _, out = lax.scan(body, init, (ids[::-1], parents[::-1]))
    return out[::-1]


def _pool2d_geom(x, kernel_size, stride, padding, ceil_mode, ch_last):
    """Shared window/stride/pad geometry for NCHW (ch_last=False) and
    NHWC pooling — one copy of the arithmetic, axis placement decided
    here (review: the NCHW/NHWC kernel pair had drifted)."""
    k = _pair(kernel_size)
    s = _pair(stride if stride is not None else kernel_size)
    p = _conv_padding(padding, 2)
    if isinstance(p, str):
        raise ValueError("string padding unsupported for pool")
    off = 1 if ch_last else 2
    if ceil_mode:
        p = [(p[i][0], p[i][1] + _ceil_extra(x.shape[off + i], k[i], s[i],
                                             p[i])) for i in range(2)]
    if ch_last:
        return ((1,) + k + (1,), (1,) + s + (1,),
                [(0, 0)] + list(p) + [(0, 0)], k, p, s)
    return ((1, 1) + k, (1, 1) + s, [(0, 0), (0, 0)] + list(p), k, p, s)


@register("max_pool2d_nhwc")
def max_pool2d_nhwc_k(x, kernel_size, stride=None, padding=0,
                      ceil_mode=False):
    win, strides, pads, _, _, _ = _pool2d_geom(x, kernel_size, stride,
                                               padding, ceil_mode, True)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
        jnp.iinfo(x.dtype).min
    return lax.reduce_window(x, init, lax.max, win, strides, pads)


@register("adaptive_avg_pool2d_nhwc")
def adaptive_avg_pool2d_nhwc_k(x, output_size):
    oh, ow = _pair(output_size)
    _, h, w, _ = x.shape
    if h % oh == 0 and w % ow == 0:
        x6 = x.reshape(x.shape[0], oh, h // oh, ow, w // ow, x.shape[3])
        return x6.mean(axis=(2, 4))
    # non-divisible: reuse the NCHW kernel's general slice-and-mean path
    out = adaptive_avg_pool2d_k(jnp.moveaxis(x, -1, 1), output_size)
    return jnp.moveaxis(out, 1, -1)


@register("s2d_stem_conv_nhwc", amp="allow")
def s2d_stem_conv_nhwc_k(x, w):
    """NHWC variant of the space-to-depth 7x7/s2 stem trick: x [b, H, W, c]
    (H, W even); w [o, c, 7, 7] (same OIHW weights as the NCHW path)."""
    b, H, W, c = x.shape
    o = w.shape[0]
    z = x.reshape(b, H // 2, 2, W // 2, 2, c)
    z = z.transpose(0, 1, 3, 2, 4, 5).reshape(b, H // 2, W // 2, c * 4)
    w8 = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))
    w4 = w8.reshape(o, c, 4, 2, 4, 2)
    # channel packing must match: z channels are (hp, wp, c)-ordered ->
    # weight taps reordered to (2, 2, c) leading
    w4 = w4.transpose(0, 3, 5, 1, 2, 4).reshape(o, 4 * c, 4, 4)
    return lax.conv_general_dilated(
        z, w4, window_strides=(1, 1), padding=((2, 1), (2, 1)),
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


@register("avg_pool2d_nhwc")
def avg_pool2d_nhwc_k(x, kernel_size, stride=None, padding=0,
                      ceil_mode=False, exclusive=True):
    win, strides, pads, k, p, _ = _pool2d_geom(x, kernel_size, stride,
                                               padding, ceil_mode, True)
    summed = lax.reduce_window(x, 0.0, lax.add, win, strides, pads)
    if exclusive and any(pi != (0, 0) for pi in p):
        counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, win,
                                   strides, pads)
        return summed / jnp.maximum(counts, 1.0)
    return summed / (k[0] * k[1])
