"""Solar-Open2 family decoder (`model_type` ``solar_open2``): a period of
one gated NoPE GQA layer and `gqa_interval` gated delta-rule layers (Kimi
Delta Attention, arXiv:2510.26692), every layer followed by sigmoid-routed
SwiGLU experts with a shared expert.  No positional encoding anywhere.

Two kinds of token mixer, two kinds of cache:

* **GQA layer** (`layer_idx` in `gqa_layers`): softmax attention, q head
  h reads kv head ``h // (heads / kv_heads)``, the output gated
  elementwise by ``sigmoid(W_g x)`` before ``W_o``.  Caches `k` and `v`
  per TOKEN, exactly as `text/llama.py` does (growing, preallocated or
  the serving pool's blocks).
* **KDA layer** (all others): ``q, k, v = SiLU(conv4(W x))`` (causal
  depthwise convolution over time), q and k L2-normalised per head, a
  per-channel decay ``a_t = exp(-exp(A_log) softplus(W_f x + dt_bias))``,
  ``beta_t = 2 sigmoid(w_beta x)`` (negative eigenvalues allowed), and

      S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  then ``W_o (RMSNorm_head(o_t) * sigmoid(W_g x))``.  Caches per REQUEST
  a float32 `state` [heads, dk, dv] and a `tail` of the last
  ``conv - 1`` rows that entered the convolution; nothing grows with the
  context.  Runs of positions go through op `kda_chunk` (chunkwise form),
  one position a row through op `kda_step` (in the serving pool: in
  place, addressed by the request's slot).

The expert layer may be one chip's SHARE of an expert-parallel layer
(`held_experts`: see `incubate.nn.moe.DroplessMoE`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..autograd import engine
from ..incubate.nn.moe import DroplessMoE
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import call as ops_call
from ..ops.dispatch import call_raw
from .decode import StatePlane
from .deepseek import _Embedding, _Norm, _Proj

_L2_EPS = 1e-6


class SolarOpen2Config:
    def __init__(self, vocab_size=196608, hidden_size=4096, num_layers=48,
                 num_heads=64, intermediate_size=10240,
                 max_position_embeddings=1048576, num_kv_heads=8,
                 head_dim=128, gqa_layers=None, gqa_interval=3,
                 use_gqa_gate=True, kda_num_heads=64, kda_head_dim=128,
                 short_conv_kernel_size=4, kda_gate_rank=128,
                 kda_allow_neg_eigval=True, moe_intermediate_size=1280,
                 n_routed_experts=320, n_shared_experts=1,
                 num_experts_per_tok=8, held_experts=None,
                 routed_scaling_factor=1.0, scoring_func="sigmoid",
                 norm_topk_prob=True, rms_norm_eps=1e-5,
                 initializer_range=0.02, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        # read by nothing: the family has no dense layer
        # (`first_k_dense_replace` 0); kept as the published key
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        if gqa_layers is None:
            gqa_layers = range(0, num_layers, gqa_interval + 1)
        self.gqa_layers = tuple(int(i) for i in gqa_layers
                                if int(i) < num_layers)
        self.use_gqa_gate = use_gqa_gate
        self.kda_num_heads = kda_num_heads
        self.kda_head_dim = kda_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_gate_rank = kda_gate_rank
        self.kda_allow_neg_eigval = kda_allow_neg_eigval
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        # (first, count): the experts this chip holds of every layer
        self.held_experts = None if held_experts is None \
            else tuple(int(n) for n in held_experts)
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype          # every parameter is born in it

    @property
    def kda_width(self):
        return self.kda_num_heads * self.kda_head_dim


class SolarOpen2Attention(nn.Layer):
    """The gated NoPE GQA layer."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _Proj(cfg, h, cfg.num_heads * d)
        self.k_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.v_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.o_proj = _Proj(cfg, cfg.num_heads * d, h)
        self.g_proj = _Proj(cfg, h, cfg.num_heads * d) \
            if cfg.use_gqa_gate else None
        self.scale = 1.0 / math.sqrt(d)

    def forward(self, x, cache=None):
        from .. import tensor_api as T
        from .decode import _update_paged_cache, _update_prealloc_cache
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, cfg.head_dim])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])
        if cache is not None and "table" in cache:
            kp, vp = _update_paged_cache(cache, k, v)
            out = ops_call("paged_attention", q, kp, vp, cache["table"],
                           cache["pos"], scale=self.scale)
        elif cache is not None and "pos" in cache:
            k, v, mask = _update_prealloc_cache(cache, k, v, s)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=0.0, training=False,
                scale=self.scale)
        else:
            if cache is not None:
                k = T.concat([cache["k"], k], axis=1)
                v = T.concat([cache["v"], v], axis=1)
                cache["k"], cache["v"] = k, v
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=(cache is None or s > 1), dropout_p=0.0,
                training=False, scale=self.scale)
        out = out.reshape([b, s, -1])
        if self.g_proj is not None:
            out = F.sigmoid(self.g_proj(x)) * out
        return self.o_proj(out)


def kda_mix(qkv, f, beta_logit, conv_w, a_log, dt_bias, state, tail,
            slots=None, pos=None, limit=None, *, heads, neg_eigval):
    """The KDA token mixer between its projections: convolution, norms,
    gates and the recurrence, with the cache carried through.

    qkv [b, s, 3 * heads * d] (the q, k and v projections side by side),
    f [b, s, heads * d] and beta_logit [b, s, heads] before their
    nonlinearities; conv_w [width, 3 * heads * d] (row ``width - 1``
    weighs the newest position); `state` and `tail` hold one entry a row
    ([b, ...]) or, with `slots` [b], are the serving pool's planes
    ([S, ...]) of which row i owns entry ``slots[i]``.  `pos`/`limit` [b]
    (the pool's forms only): the run starts at context offset `pos` --
    from the zero state where that is 0 -- and positions at or past
    `limit` are padding: a row with ``limit == 0`` is dead and leaves
    its entry as it was.  Every row of one call needs a slot of its own.
    Returns (o [b, s, heads, d] float32, state, tail)."""
    f32 = jnp.float32
    b, s, width3 = qkv.shape
    d = width3 // (3 * heads)
    taps = conv_w.shape[0]
    pooled = slots is not None
    if pooled:
        slots = slots.astype(jnp.int32)
        fresh = (pos == 0)
        tail0 = jnp.where(fresh[:, None, None], 0, tail[slots])
        n_real = (limit - pos).astype(jnp.int32)
    else:
        tail0 = tail
        n_real = jnp.full((b,), s, jnp.int32)
    # the causal depthwise convolution over [tail | run]
    window = jnp.concatenate([tail0.astype(qkv.dtype), qkv], axis=1)
    w = conv_w.astype(f32)
    mixed = sum(window[:, j:j + s].astype(f32) * w[j] for j in range(taps))
    mixed = jax.nn.silu(mixed).reshape(b, s, 3, heads, d)
    # the rows that entered it last: the real positions' end
    tail1 = jax.vmap(lambda win, n: jax.lax.dynamic_slice_in_dim(
        win, n, taps - 1, 0))(window, jnp.maximum(n_real, 0))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)

    q = unit(mixed[:, :, 0]) * (d ** -0.5)
    k = unit(mixed[:, :, 1])
    v = mixed[:, :, 2]
    g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        f.astype(f32) + dt_bias.astype(f32)).reshape(b, s, heads, d)
    beta = jax.nn.sigmoid(beta_logit.astype(f32))
    if neg_eigval:
        beta = 2.0 * beta
    if s == 1:
        live = n_real > 0
        if pooled:      # a one-token prompt: decay the slot's old state to 0
            g = jnp.where(fresh[:, None, None, None], -jnp.inf, g)
        else:
            slots = jnp.arange(b, dtype=jnp.int32)
        o, state = call_raw("kda_step", q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0], state, slots, live)
        o = o[:, None]
    else:
        s0 = jnp.where(fresh[:, None, None, None], 0, state[slots]) \
            if pooled else state
        o, s1 = call_raw("kda_chunk", q, k, v, g, beta, s0,
                         n_valid=n_real if pooled else None)
        state = state.at[slots].set(s1.astype(state.dtype)) if pooled \
            else s1
        live = jnp.ones((b,), bool)
    if pooled:
        at = jnp.where(live, slots, tail.shape[0])
        tail = tail.at[at].set(tail1.astype(tail.dtype), mode="drop")
    else:
        tail = tail1.astype(tail.dtype)
    return o, state, tail


class SolarOpen2KDA(nn.Layer):
    """The gated delta-rule layer (Kimi Delta Attention)."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        h, width, rank = cfg.hidden_size, cfg.kda_width, cfg.kda_gate_rank
        self.qkv_proj = _Proj(cfg, h, 3 * width)
        self.conv_weight = self.create_parameter(
            [cfg.short_conv_kernel_size, 3 * width],
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        # kda_use_full_proj false: the decay and the output gate are
        # low-rank pairs
        self.f_a_proj = _Proj(cfg, h, rank)
        self.f_b_proj = _Proj(cfg, rank, width)
        self.g_a_proj = _Proj(cfg, h, rank)
        self.g_b_proj = _Proj(cfg, rank, width)
        self.b_proj = _Proj(cfg, h, cfg.kda_num_heads)
        self.A_log = self.create_parameter(
            [cfg.kda_num_heads], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [width], is_bias=True, default_initializer=I.Constant(0.0))
        self.o_norm = _Norm(cfg, cfg.kda_head_dim)
        self.o_proj = _Proj(cfg, width, h)

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, d = cfg.kda_num_heads, cfg.kda_head_dim
        carried = cache if cache is not None else _zero_state(cfg, b, x.dtype)
        args = [self.qkv_proj(x), self.f_b_proj(self.f_a_proj(x)),
                self.b_proj(x), self.conv_weight, self.A_log, self.dt_bias,
                carried["state"], carried["tail"]]
        if cache is not None and "slot" in cache:
            args += [cache["slot"], cache["pos"], cache["limit"]]
        o, state, tail = engine.apply(
            "kda_mix", kda_mix, args,
            {"heads": heads, "neg_eigval": cfg.kda_allow_neg_eigval})
        if cache is not None:
            cache["state"], cache["tail"] = state, tail
        o = self.o_norm(o.astype(x.dtype)).reshape([b, s, heads * d])
        gate = self.g_b_proj(self.g_a_proj(x))
        return self.o_proj(o * F.sigmoid(gate))


def _zero_state(cfg, batch, dtype):
    from .. import tensor_api as T
    return {"state": T.zeros([batch, cfg.kda_num_heads, cfg.kda_head_dim,
                              cfg.kda_head_dim], dtype="float32"),
            "tail": T.zeros([batch, cfg.short_conv_kernel_size - 1,
                             3 * cfg.kda_width], dtype=dtype)}


class SolarOpen2Block(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config, layer_idx):
        super().__init__()
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.kind = "gqa" if layer_idx in cfg.gqa_layers else "kda"
        self.mixer = SolarOpen2Attention(cfg) if self.kind == "gqa" \
            else SolarOpen2KDA(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            scoring=cfg.scoring_func, score_bias=True,
            norm_topk=cfg.norm_topk_prob,
            route_scale=cfg.routed_scaling_factor,
            num_shared=cfg.n_shared_experts,
            init_std=cfg.initializer_range, dtype=cfg.dtype,
            held=cfg.held_experts)

    def forward(self, x, cache=None):
        x = x + self.mixer(self.input_layernorm(x), cache=cache)
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


class SolarOpen2Model(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Embedding(cfg)
        self.layers = nn.LayerList(
            [SolarOpen2Block(cfg, i) for i in range(cfg.num_layers)])
        self.norm = _Norm(cfg, cfg.hidden_size)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        for i, block in enumerate(self.layers):
            x = block(x, cache=caches[i] if caches is not None else None)
        return self.norm(x)


class SolarOpen2ForCausalLM(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.model = SolarOpen2Model(cfg)
        self.lm_head = _Proj(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, caches=None):
        return self.lm_head(self.model(input_ids, caches))

    cache_op = "paged_attention"    # the op that reads the block planes

    def cache_planes(self):
        """What each layer caches, for the serving pool: `k` and `v` per
        token in a GQA layer; per REQUEST, in a KDA layer, the float32
        state and the convolution's tail."""
        cfg = self.cfg
        per_token = {"k": (cfg.num_kv_heads, cfg.head_dim),
                     "v": (cfg.num_kv_heads, cfg.head_dim)}
        per_request = {
            "state": StatePlane((cfg.kda_num_heads, cfg.kda_head_dim,
                                 cfg.kda_head_dim), "float32"),
            "tail": StatePlane((cfg.short_conv_kernel_size - 1,
                                3 * cfg.kda_width), None)}
        return [per_token if i in cfg.gqa_layers else per_request
                for i in range(cfg.num_layers)]

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        from .. import tensor_api as T
        cfg = self.cfg
        length = 0 if max_length is None else max_length
        caches = []
        for i in range(cfg.num_layers):
            if i in cfg.gqa_layers:
                c = {n: T.zeros([batch_size, length, cfg.num_kv_heads,
                                 cfg.head_dim], dtype=dtype)
                     for n in ("k", "v")}
                if max_length is not None:
                    c["pos"] = T.zeros([], dtype="int32")
            else:
                c = _zero_state(cfg, batch_size, dtype)
            caches.append(c)
        return caches

    def generate(self, input_ids, max_new_tokens=20, use_jit=True, **kw):
        if use_jit:
            from .decode import jit_generate
            return jit_generate(self, input_ids,
                                max_new_tokens=max_new_tokens, **kw)
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens, **kw)
