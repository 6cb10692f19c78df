"""Keye-VL-2.0 language model (`model_type` ``KeyeVL2``): the Qwen3 MoE
block with DeepSeek-V3.2's learned sparse attention.

Each layer is pre-norm under RMSNorm:

* **attention**: GQA, `num_heads` (32) query heads over `num_kv_heads`
  (4) kv heads of `head_dim` (128); an RMSNorm over each head's dims on
  q and k before RoPE (θ `rope_theta`, pairs ``(i, i + d/2)`` over the
  whole head).  Each query attends only the `index_topk` (2,048) cached
  positions a LIGHTNING INDEXER picks (all of them while it sees fewer):
  the indexer's `index_n_heads` (16) queries of `index_head_dim` (64)
  meet ONE cached key a position, ``k^I = LayerNorm(W_k u)``, RoPE on the
  first `index_rope_dim` (32) dims of both, and score
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])`` in float32, with
  ``w = W_w u / sqrt(16 * 64)``.  Every head of a query shares its picks.
* **experts**: softmax-routed SwiGLU experts, top `num_experts_per_tok`
  (8) of `num_experts` (128), weights renormalised over the picks, no
  shared expert (`incubate.nn.moe.DroplessMoE`).

The indexer's key is cached beside K and V, zero-padded to 128 lanes
(`index_cache_width`): what a 64-wide bfloat16 row takes in the TPU's
tiled memory anyway, and what lets a kernel take a pool block as one
aligned tile.  Its queries are padded alike, so that the products are
the same.

Three cache forms, as the other K/V models: growing (concatenate),
preallocated, and the serving pool's blocks, where the op
`sparse_paged_attention` scores, picks and attends; the forms without a
pool pick by a mask over `sdpa`.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import nn
from ..autograd import engine
from ..incubate.nn.moe import DroplessMoE
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import call as ops_call
from ..ops.nn_kernels import indexer_scores, sparse_select
from .decode import LayerPlanes
from .deepseek import _Embedding, _Norm, _Proj
from .laguna import _rope, rope_frequencies

_LANES = 128


class KeyeVL2Config:
    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, intermediate_size=6144,
                 max_position_embeddings=262144, num_kv_heads=4,
                 head_dim=128, rope_theta=10000000.0, rms_norm_eps=1e-6,
                 num_experts=128, num_experts_per_tok=8,
                 moe_intermediate_size=768, norm_topk_prob=True,
                 index_n_heads=16, index_head_dim=64, index_topk=2048,
                 index_rope_dim=32, index_norm_eps=1e-6,
                 initializer_range=0.02, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        # the dense width of the published config; every layer here is
        # routed (`mlp_only_layers` is empty), so nothing uses it
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = int(index_topk)
        self.index_rope_dim = index_rope_dim
        self.index_norm_eps = index_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype          # every parameter is born in it

    @property
    def index_cache_width(self):
        """Width of a cached indexer key: `index_head_dim` padded to
        lanes."""
        return -(-self.index_head_dim // _LANES) * _LANES


def _positions(start, s):
    """Absolute positions [b | 1, s] from `start`: a number, a scalar
    or [b] offsets."""
    from .. import tensor_api as T
    if not hasattr(start, "shape"):
        start = T.full([], start, dtype="int32")
    base = start.astype("int32")
    base = base.reshape([-1, 1]) if base.ndim else base.reshape([1, 1])
    return base + T.arange(0, s, dtype="int32").unsqueeze(0)


class KeyeIndexer(nn.Layer):
    """The lightning indexer's weights: `wq` [hidden, heads x dim], `wk`
    [hidden, dim] under a LayerNorm (`k_norm`), `weights_proj`
    [hidden, heads]."""

    def __init__(self, cfg: KeyeVL2Config):
        super().__init__(dtype=cfg.dtype)
        h, n, d = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
        self.cfg = cfg
        self.wq = _Proj(cfg, h, n * d)
        self.wk = _Proj(cfg, h, d)
        self.weights_proj = _Proj(cfg, h, n)
        self.k_norm_weight = self.create_parameter(
            [d], default_initializer=I.Constant(1.0))
        self.k_norm_bias = self.create_parameter(
            [d], is_bias=True, default_initializer=I.Constant(0.0))
        self.inv = rope_frequencies({"rope_theta": cfg.rope_theta},
                                    cfg.index_rope_dim)[0]

    def forward(self, u, positions):
        """(q^I [b, s, heads, W], w [b, s, heads] float32, k^I [b, s, W])
        of the layer's normed input, W the cache width."""
        cfg = self.cfg
        b, s, _ = u.shape
        n, d, r = cfg.index_n_heads, cfg.index_head_dim, cfg.index_rope_dim
        pad = cfg.index_cache_width - d
        inv, eps = self.inv, cfg.index_norm_eps
        scale = (n * d) ** -0.5

        def fn(q_, k_, w_, g, bias, p):
            f32 = jnp.float32
            kf = k_.astype(f32)
            mu = kf.mean(-1, keepdims=True)
            var = jnp.square(kf - mu).mean(-1, keepdims=True)
            kf = (kf - mu) * (var + eps) ** -0.5 * g.astype(f32) \
                + bias.astype(f32)
            q4 = _rope(q_.reshape(b, s, n, d), p, inv, r, 1.0)
            k3 = _rope(kf.astype(k_.dtype)[:, :, None, :], p, inv, r,
                       1.0)[:, :, 0]
            zeros = lambda a: jnp.zeros(a.shape[:-1] + (pad,), a.dtype)
            return (jnp.concatenate([q4, zeros(q4)], -1),
                    w_.astype(f32) * scale,
                    jnp.concatenate([k3, zeros(k3)], -1))

        return engine.apply(
            "keye_indexer", fn,
            [self.wq(u), self.wk(u), self.weights_proj(u),
             self.k_norm_weight, self.k_norm_bias, positions])


def _select_mask(q_idx, w, keys, seen, topk):
    """The mask [b, 1, s, L] of what each query attends over whole
    caches: what it sees and its indexer picks; `seen` [1 | b, s, L]."""
    def fn(q_, w_, k_, seen_):
        seen_ = jnp.broadcast_to(seen_, q_.shape[:1] + seen_.shape[1:])
        scores = jnp.where(seen_, indexer_scores(q_, w_, k_), -jnp.inf)
        return (seen_ & sparse_select(scores, topk))[:, None]
    return engine.apply("keye_select", fn, [q_idx, w, keys, seen])


class KeyeAttention(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _Proj(cfg, h, cfg.num_heads * d)
        self.k_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.v_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.o_proj = _Proj(cfg, cfg.num_heads * d, h)
        self.q_norm = _Norm(cfg, d)
        self.k_norm = _Norm(cfg, d)
        self.indexer = KeyeIndexer(cfg)
        self.inv = rope_frequencies({"rope_theta": cfg.rope_theta}, d)[0]
        self.scale = 1.0 / math.sqrt(d)

    def forward(self, x, cache=None):
        from .. import tensor_api as T
        from .decode import _update_paged_cache, _update_prealloc_cache
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        start = 0 if cache is None else cache["pos"] if "pos" in cache \
            else cache["k"].shape[1]
        positions = _positions(start, s)
        q = self.q_norm(self.q_proj(x).reshape([b, s, cfg.num_heads, d]))
        k = self.k_norm(self.k_proj(x).reshape([b, s, cfg.num_kv_heads, d]))
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, d])
        inv = self.inv
        q, k = engine.apply(
            "keye_rope", lambda q_, k_, p: (_rope(q_, p, inv, d, 1.0),
                                            _rope(k_, p, inv, d, 1.0)),
            [q, k, positions])
        q_idx, w, ik = self.indexer(x, positions)
        topk = cfg.index_topk
        if cache is not None and "table" in cache:
            kp, vp = _update_paged_cache(cache, k, v)
            bs = cache["ik"].shape[1]
            cache["ik"] = ops_call("paged_write", cache["ik"], ik,
                                   cache["table"], cache["pos"],
                                   cache["limit"], block_size=bs)
            out = ops_call("sparse_paged_attention", q, kp, vp, cache["ik"],
                           q_idx, w, cache["table"], cache["pos"],
                           topk=topk, scale=self.scale)
            return self.o_proj(out.reshape([b, s, -1]))
        if cache is not None and "pos" in cache:
            cache["ik"] = ops_call("dyn_update_seq", cache["ik"], ik,
                                   cache["pos"])
            k, v, seen = _update_prealloc_cache(cache, k, v, s)
            keys = cache["ik"]
            seen = seen.squeeze(1)
        else:
            keys = ik
            if cache is not None:
                k = T.concat([cache["k"], k], axis=1)
                v = T.concat([cache["v"], v], axis=1)
                keys = T.concat([cache["ik"], ik], axis=1)
                cache["k"], cache["v"], cache["ik"] = k, v, keys
            length = k.shape[1]
            cols = T.arange(length, dtype="int32").unsqueeze(0)
            rows = (length - s + T.arange(s, dtype="int32")).unsqueeze(1)
            seen = (cols <= rows).unsqueeze(0)
        mask = _select_mask(q_idx, w, keys, seen, topk)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask, dropout_p=0.0, training=False,
            scale=self.scale)
        return self.o_proj(out.reshape([b, s, -1]))


class KeyeBlock(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = KeyeAttention(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, scoring="softmax", score_bias=False,
            norm_topk=cfg.norm_topk_prob, num_shared=0,
            init_std=cfg.initializer_range, dtype=cfg.dtype)

    def forward(self, x, cache=None):
        x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        m = self.post_attention_layernorm(x)
        if cache is not None and "limit" in cache:
            # a pooled cache says which tokens are real (deepseek.py)
            from .. import tensor_api as T
            at = cache["pos"].astype("int32").unsqueeze(1) \
                + T.arange(x.shape[1], dtype="int32").unsqueeze(0)
            y, cache["expert_load"] = self.mlp(
                m, live=(at < cache["limit"].unsqueeze(1)).reshape([-1]))
            return x + y
        return x + self.mlp(m)


class KeyeVL2Model(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Embedding(cfg)
        self.layers = nn.LayerList(
            [KeyeBlock(cfg) for _ in range(cfg.num_layers)])
        self.norm = _Norm(cfg, cfg.hidden_size)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        for i, block in enumerate(self.layers):
            x = block(x, cache=caches[i] if caches is not None else None)
        return self.norm(x)


class KeyeVL2ForCausalLM(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__()
        self.cfg = cfg
        self.model = KeyeVL2Model(cfg)
        self.lm_head = _Proj(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, caches=None):
        return self.lm_head(self.model(input_ids, caches))

    cache_op = "sparse_paged_attention"     # the op that reads the planes

    @property
    def cache_op_args(self):
        """What the op's counts need beyond the pool's shapes."""
        return {"topk": self.cfg.index_topk,
                "index_heads": self.cfg.index_n_heads}

    def cache_planes(self):
        """`k` and `v` per token in every layer, and the indexer's key."""
        cfg = self.cfg
        kv = (cfg.num_kv_heads, cfg.head_dim)
        return [LayerPlanes({"k": kv, "v": kv,
                             "ik": (cfg.index_cache_width,)})
                for _ in range(cfg.num_layers)]

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        from .. import tensor_api as T
        cfg = self.cfg
        length = 0 if max_length is None else max_length
        kv = [batch_size, length, cfg.num_kv_heads, cfg.head_dim]
        caches = []
        for _ in range(cfg.num_layers):
            c = {"k": T.zeros(kv, dtype=dtype), "v": T.zeros(kv, dtype=dtype),
                 "ik": T.zeros([batch_size, length, cfg.index_cache_width],
                               dtype=dtype)}
            if max_length is not None:
                c["pos"] = T.zeros([], dtype="int32")
            caches.append(c)
        return caches

    def generate(self, input_ids, max_new_tokens=20, use_jit=True, **kw):
        if use_jit:
            from .decode import jit_generate
            return jit_generate(self, input_ids,
                                max_new_tokens=max_new_tokens, **kw)
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens, **kw)
