"""DeepSeek-V3 family decoder (arXiv:2412.19437 section 2.1; also the
language model of Kimi-VL and Moonlight): multi-head latent attention
(MLA) and sigmoid-routed SwiGLU experts with shared experts.

Attention caches ONE latent row a token a layer, ``[c | k_rope]`` (the
normalised compressed KV of width `kv_lora_rank` and the rotary key all
heads share), padded with zeros to a multiple of 128 lanes: what lies in
the TPU's tiled memory anyway, and what lets a decode kernel take a pool
block as one aligned tile (`cache_width`).  Two forms of the same
mathematics, the published split:

* **expanded** (plain forward, and the preallocated and growing caches):
  K and V of the visible context are materialised from the latent rows
  through ``W_kvb`` and attended by `sdpa`;
* **absorbed** (everything over the serving pool: a decode step's one
  token a row and a prefill chunk's many): ``W_kvb``'s key half moves
  into the query and its value half behind the softmax, so the scores
  and the weighted sum run on the latent rows themselves (op
  `latent_paged_attention`, whose kernels walk a row's live blocks).

RoPE turns interleaved pairs ``(2i, 2i + 1)`` (as `text/llama.py`).
`q_lora_rank` (a low-rank query) is not implemented: the published
configurations this file serves have none.
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
from ..ops.nn_kernels import paged_visible
from .llama import _rope

_LANES = 128


class DeepseekV3Config:
    def __init__(self, vocab_size=163840, hidden_size=2048, num_layers=27,
                 num_heads=16, intermediate_size=11264,
                 max_position_embeddings=131072, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 moe_intermediate_size=1408, n_routed_experts=64,
                 n_shared_experts=2, num_experts_per_tok=6,
                 first_k_dense_replace=1, routed_scaling_factor=2.446,
                 scoring_func="sigmoid", norm_topk_prob=True,
                 rope_theta=800000.0, rms_norm_eps=1e-5,
                 initializer_range=0.02, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        # every parameter is BORN in this dtype (under LazyGuard too): a
        # cast afterwards would hold both copies at once
        self.dtype = dtype

    @property
    def cache_width(self):
        """Width of a cached latent row: [c | k_rope] padded to lanes."""
        used = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-used // _LANES) * _LANES


class _Proj(nn.Layer):
    """y = x @ W with W [in, out] born in the configuration's dtype."""

    def __init__(self, cfg, in_features, out_features):
        super().__init__(dtype=cfg.dtype)
        self.weight = self.create_parameter(
            [in_features, out_features],
            default_initializer=I.Normal(0.0, cfg.initializer_range))

    def forward(self, x):
        return F.linear(x, self.weight)


class _Norm(nn.Layer):
    def __init__(self, cfg, width):
        super().__init__(dtype=cfg.dtype)
        self.epsilon = cfg.rms_norm_eps
        self.weight = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon)


class _Embedding(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.weight = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size],
            default_initializer=I.Normal(0.0, cfg.initializer_range))

    def forward(self, ids):
        return F.embedding(ids, self.weight)


def _rope_one(x, positions, theta):
    """`llama._rope` on one [b, s, h, d] array."""
    return _rope(x, x, positions, theta)[0]


def _paged_mask(s, length, pos):
    """`paged_visible` as an sdpa mask [b, 1, s, length]."""
    return paged_visible(s, length, pos)[:, None]


class DeepseekV3Attention(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.num_heads
        self.qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_proj = _Proj(cfg, h, heads * self.qk_dim)
        self.kv_a_proj = _Proj(cfg, h,
                               cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = _Norm(cfg, cfg.kv_lora_rank)
        self.kv_b_proj = _Proj(
            cfg, cfg.kv_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _Proj(cfg, heads * cfg.v_head_dim, h)
        self.scale = 1.0 / math.sqrt(self.qk_dim)

    # ------------------------------------------------------------ pieces
    def _queries(self, x, positions):
        """[b, s, heads, nope + rope], the rotary part turned."""
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, self.qk_dim])
        nope = cfg.qk_nope_head_dim
        return engine.apply(
            "mla_rope_q",
            lambda q_, p: jnp.concatenate(
                [q_[..., :nope],
                 _rope_one(q_[..., nope:], p,
                           cfg.rope_theta).astype(q_.dtype)], -1),
            [q, positions])

    def _latent(self, x, positions):
        """The rows to cache: [b, s, cache_width] = [norm(c) | rope(k_r) |
        zeros]."""
        cfg = self.cfg
        lora = cfg.kv_lora_rank
        ckr = self.kv_a_proj(x)
        c = self.kv_a_layernorm(ckr[:, :, :lora])
        pad = cfg.cache_width - lora - cfg.qk_rope_head_dim
        return engine.apply(
            "mla_latent",
            lambda c_, kr, p: jnp.concatenate(
                [c_, _rope_one(kr[:, :, None, :], p,
                               cfg.rope_theta)[:, :, 0, :].astype(c_.dtype),
                 jnp.zeros(c_.shape[:2] + (pad,), c_.dtype)], -1),
            [c, ckr[:, :, lora:], positions])

    def _expanded(self, q, rows, mask=None, is_causal=False):
        """Attention of q [b, s, heads, qk] over latent `rows`
        [b, L, cache_width] with K and V materialised from them."""
        cfg = self.cfg
        b, length, _ = rows.shape
        heads, nope = cfg.num_heads, cfg.qk_nope_head_dim
        lora, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        kv = self.kv_b_proj(rows[:, :, :lora]).reshape(
            [b, length, heads, nope + cfg.v_head_dim])
        k = engine.apply(
            "mla_expand_k",
            lambda kv_, r: jnp.concatenate(
                [kv_[..., :nope], jnp.broadcast_to(
                    r[:, :, None, lora:lora + rope],
                    (b, length, heads, rope))], -1), [kv, rows])
        out = F.scaled_dot_product_attention(
            q, k, kv[:, :, :, nope:], attn_mask=mask, is_causal=is_causal,
            dropout_p=0.0, training=False, scale=self.scale)
        return out.reshape([b, q.shape[1], heads * cfg.v_head_dim])

    def _absorbed(self, q, pool, table, pos):
        """A row's tokens (s of them from context offset `pos`) against
        the latent pool: W_kvb's key half goes into the query, its value
        half behind the weighted sum."""
        cfg = self.cfg
        heads, nope, vd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        lora, width = cfg.kv_lora_rank, cfg.cache_width
        b, s = q.shape[0], q.shape[1]

        def absorb(q_, wkvb):
            w = wkvb.reshape(lora, heads, nope + vd)
            qc = jnp.einsum("bshd,lhd->bshl", q_[..., :nope], w[..., :nope])
            pad = width - lora - (q_.shape[-1] - nope)
            return jnp.concatenate(
                [qc.astype(q_.dtype), q_[..., nope:],
                 jnp.zeros(q_.shape[:3] + (pad,), q_.dtype)], -1)

        qa = engine.apply("mla_absorb_q", absorb, [q, self.kv_b_proj.weight])
        oc = ops_call("latent_paged_attention", qa, pool, table, pos,
                      value_dim=lora, scale=self.scale)
        return engine.apply(
            "mla_absorb_v",
            lambda o_, wkvb: jnp.einsum(
                "bshl,lhd->bshd", o_,
                wkvb.reshape(lora, heads, nope + vd)[..., nope:]).reshape(
                    b, s, heads * vd).astype(o_.dtype),
            [oc, self.kv_b_proj.weight])

    # ----------------------------------------------------------- forward
    def forward(self, x, cache=None):
        from .. import tensor_api as T
        b, s, _ = x.shape
        if cache is None:
            positions = T.arange(0, s, dtype="int32").unsqueeze(0)
            out = self._expanded(self._queries(x, positions),
                                 self._latent(x, positions), is_causal=True)
            return self.o_proj(out)
        if "table" in cache:
            # serving pool: write THEN attend, as the K/V models do
            pos = cache["pos"]
            positions = pos.astype("int32").unsqueeze(1) \
                + T.arange(0, s, dtype="int32").unsqueeze(0)
            q = self._queries(x, positions)
            bs = cache["kv"].shape[1]
            cache["kv"] = ops_call(
                "paged_write", cache["kv"], self._latent(x, positions),
                cache["table"], pos, cache["limit"], block_size=bs)
            out = self._absorbed(q, cache["kv"], cache["table"], pos)
            return self.o_proj(out)
        if "pos" in cache:
            # preallocated rows (jitted decode): write at the offset,
            # attend under the length mask
            pos = cache["pos"].astype("int32")
            base = pos.reshape([-1, 1]) if pos.ndim else pos.reshape([1, 1])
            positions = base + T.arange(0, s, dtype="int32").unsqueeze(0)
            cache["kv"] = ops_call("dyn_update_seq", cache["kv"],
                                   self._latent(x, positions), cache["pos"])
            rows = cache["kv"]
            mask = engine.apply(
                "paged_mask",
                lambda p, s_, n: _paged_mask(s_, n, jnp.atleast_1d(p)),
                [pos], {"s_": s, "n": rows.shape[1]})
            out = self._expanded(self._queries(x, positions), rows,
                                 mask=mask)
            return self.o_proj(out)
        # growing cache (eager decode): concatenate, causal in the window
        offset = cache["kv"].shape[1]
        positions = T.arange(offset, offset + s, dtype="int32").unsqueeze(0)
        rows = T.concat([cache["kv"], self._latent(x, positions)], axis=1)
        cache["kv"] = rows
        out = self._expanded(self._queries(x, positions), rows,
                             is_causal=(s > 1))
        return self.o_proj(out)


class DeepseekV3MLP(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.gate_proj = _Proj(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.up_proj = _Proj(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.down_proj = _Proj(cfg, cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class DeepseekV3Block(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config, layer_idx):
        super().__init__()
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = DeepseekV3Attention(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.routed = layer_idx >= cfg.first_k_dense_replace
        if self.routed:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                scoring=cfg.scoring_func, score_bias=True,
                norm_topk=cfg.norm_topk_prob,
                route_scale=cfg.routed_scaling_factor,
                num_shared=cfg.n_shared_experts,
                init_std=cfg.initializer_range, dtype=cfg.dtype)
        else:
            self.mlp = DeepseekV3MLP(cfg)

    def forward(self, x, cache=None):
        x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        m = self.post_attention_layernorm(x)
        if self.routed and cache is not None and "limit" in cache:
            # a paged cache says which of the tokens are real (a row's run
            # from `pos` up to `limit`; a dead decode slot has limit 0):
            # beside the result, how many of them each expert received
            from .. import tensor_api as T
            at = cache["pos"].astype("int32").unsqueeze(1) \
                + T.arange(x.shape[1], dtype="int32").unsqueeze(0)
            y, cache["expert_load"] = self.mlp(
                m, live=(at < cache["limit"].unsqueeze(1)).reshape([-1]))
            return x + y
        return x + self.mlp(m)


class DeepseekV3Model(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Embedding(cfg)
        self.layers = nn.LayerList(
            [DeepseekV3Block(cfg, i) for i in range(cfg.num_layers)])
        self.norm = _Norm(cfg, cfg.hidden_size)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        for i, block in enumerate(self.layers):
            x = block(x, cache=caches[i] if caches is not None else None)
        return self.norm(x)


class DeepseekV3ForCausalLM(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV3Model(cfg)
        self.lm_head = _Proj(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, caches=None):
        return self.lm_head(self.model(input_ids, caches))

    cache_op = "latent_paged_attention"     # the op that reads the planes

    def cache_planes(self):
        """What a layer caches per token, for the serving pool: ONE
        latent row."""
        return [{"kv": (self.cfg.cache_width,)}] * self.cfg.num_layers

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        from .. import tensor_api as T
        length = 0 if max_length is None else max_length
        caches = []
        for _ in range(self.cfg.num_layers):
            c = {"kv": T.zeros([batch_size, length, self.cfg.cache_width],
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
