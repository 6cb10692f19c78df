"""Laguna family decoder (`model_type` ``laguna``): pre-norm residual
blocks under RMSNorm whose attention layers come in two KINDS that
differ in more than a mask, in periods of one full layer and three
window layers (`layer_types`):

* **full_attention**: `num_attention_heads_per_layer[i]` (48) query
  heads over `num_kv_heads` (8) kv heads, causal over the whole context;
  RoPE turns the first HALF of every head (`partial_rotary_factor` 0.5)
  at YaRN's blended frequencies, cos and sin scaled by
  `attention_factor`.
* **sliding_attention**: 72 query heads over the same 8 kv heads, a row
  sees its last `sliding_window` (512) positions alone; plain RoPE over
  the whole head.

Every layer gates each head's output by one scalar, ``sigmoid(W_g u)``
from the layer's normed input (`gating: per-head`), before ``W_o``.
Layers in `mlp_only_layers` carry a dense SwiGLU, the others
sigmoid-routed SwiGLU experts with one shared expert
(`incubate.nn.moe.DroplessMoE`), of which this chip may hold a SHARE
(`held_experts`).

RoPE turns the pairs ``(i, i + r/2)`` of the rotary part ``r`` (the
published family's `rotate_half`), not `text/llama.py`'s interleaved
``(2i, 2i + 1)``.

Three cache forms, as the other K/V models: growing (concatenate),
preallocated (`_update_prealloc_cache(window=)`), the serving pool's
blocks.  `cache_planes()` names each layer's KIND and window, so that
the pool gives the window layers blocks of their own lifetime
(`text.decode.LayerPlanes`).
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..autograd import engine
from ..incubate.nn.moe import DroplessMoE
from ..nn import functional as F
from ..ops import call as ops_call
from .decode import LayerPlanes
from .deepseek import DeepseekV3MLP, _Embedding, _Norm, _Proj

FULL, WINDOW = "full_attention", "sliding_attention"
_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 128.0,
           "original_max_position_embeddings": 8192, "beta_fast": 32.0,
           "beta_slow": 1.0, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000.0,
             "partial_rotary_factor": 1.0},
}


class LagunaConfig:
    def __init__(self, vocab_size=100352, hidden_size=3072, num_layers=48,
                 num_heads=48, intermediate_size=12288,
                 max_position_embeddings=1048576, num_kv_heads=8,
                 head_dim=128, layer_types=None,
                 num_attention_heads_per_layer=None, rope_parameters=None,
                 sliding_window=512, gating="per-head", mlp_only_layers=(0,),
                 num_experts=256, num_experts_per_tok=10,
                 moe_intermediate_size=1024,
                 shared_expert_intermediate_size=1024, held_experts=None,
                 moe_routed_scaling_factor=2.5, norm_topk_prob=True,
                 rms_norm_eps=1e-6, initializer_range=0.02, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads          # a full layer's; see heads_of
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        if layer_types is None:             # one full layer, three window
            layer_types = [FULL if i % 4 == 0 else WINDOW
                           for i in range(num_layers)]
        self.layer_types = tuple(layer_types[:num_layers])
        if len(self.layer_types) != num_layers or \
                set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types {layer_types!r} does not name "
                             f"the kind of {num_layers} layers")
        if num_attention_heads_per_layer is None:
            num_attention_heads_per_layer = [
                num_heads if t == FULL else num_heads * 3 // 2
                for t in self.layer_types]
        self.num_attention_heads_per_layer = tuple(
            int(n) for n in num_attention_heads_per_layer[:num_layers])
        self.rope_parameters = {kind: dict(_ROPE[kind],
                                           **(rope_parameters or {}).get(
                                               kind, {}))
                                for kind in (FULL, WINDOW)}
        self.sliding_window = int(sliding_window)
        if gating not in ("per-head", None):
            raise ValueError(f"unknown gating {gating!r}")
        self.gating = gating
        self.mlp_only_layers = tuple(int(i) for i in mlp_only_layers)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        if shared_expert_intermediate_size % moe_intermediate_size:
            raise ValueError("the shared expert is whole experts wide")
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        # (first, count): the experts this chip holds of every layer
        self.held_experts = None if held_experts is None \
            else tuple(int(n) for n in held_experts)
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype          # every parameter is born in it

    def heads_of(self, layer_idx):
        return self.num_attention_heads_per_layer[layer_idx]

    def window_of(self, layer_idx):
        """The layer's band, None where it sees the whole context."""
        return self.sliding_window \
            if self.layer_types[layer_idx] == WINDOW else None


def rope_frequencies(params, head_dim):
    """(inv_freq [r / 2] float32, r, the factor on cos and sin) of one
    kind's `rope_parameters`: plain ``theta ** (-2i / r)``, or YaRN's
    blend of it with its `factor`-fold slowing, fixed once (the published
    ``rope_type: yarn`` initialisation over the rotary width r)."""
    r = int(head_dim * float(params.get("partial_rotary_factor", 1.0)))
    theta = float(params["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / r)
    if params.get("rope_type", "default") == "default":
        return inv.astype(np.float32), r, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    orig = float(params["original_max_position_embeddings"])

    def turns(beta):    # the dim whose wave turns `beta` times in `orig`
        return r * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turns(float(params["beta_fast"]))), 0)
    hi = min(math.ceil(turns(float(params["beta_slow"]))), r - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp       # 1: the wave as it was; 0: slowed
    inv = (1.0 - keep) * inv / float(params["factor"]) + keep * inv
    return (inv.astype(np.float32), r,
            float(params.get("attention_factor", 1.0)))


def _rope(x, positions, inv_freq, r, factor):
    """Turn the first `r` dims of every head of x [b, s, h, d] at
    `positions` [b | 1, s], pairs ``(i, i + r/2)``; float32 inside."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :r // 2], xf[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., r:]],
        -1).astype(x.dtype)


class LagunaAttention(nn.Layer):
    def __init__(self, cfg: LagunaConfig, layer_idx):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.heads = cfg.heads_of(layer_idx)
        self.window = cfg.window_of(layer_idx)
        self.q_proj = _Proj(cfg, h, self.heads * d)
        self.k_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.v_proj = _Proj(cfg, h, cfg.num_kv_heads * d)
        self.o_proj = _Proj(cfg, self.heads * d, h)
        self.g_proj = _Proj(cfg, h, self.heads) if cfg.gating else None
        self.rope = rope_frequencies(
            cfg.rope_parameters[cfg.layer_types[layer_idx]], d)
        self.scale = 1.0 / math.sqrt(d)

    def _turn(self, q, k, start):
        """RoPE at positions ``start + [0, s)``; `start` a number, a
        scalar or [b] offsets."""
        inv, r, factor = self.rope

        def fn(q_, k_, p):
            pos = jnp.atleast_1d(p.astype(jnp.int32))[:, None] \
                + jnp.arange(q_.shape[1], dtype=jnp.int32)[None, :]
            return (_rope(q_, pos, inv, r, factor),
                    _rope(k_, pos, inv, r, factor))

        if not hasattr(start, "shape"):
            from .. import tensor_api as T
            start = T.full([], start, dtype="int32")
        return engine.apply("laguna_rope", fn, [q, k, start])

    def forward(self, x, cache=None):
        from .. import tensor_api as T
        from .decode import _update_paged_cache, _update_prealloc_cache
        cfg, W = self.cfg, self.window
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.heads, cfg.head_dim])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, cfg.head_dim])
        if cache is not None and "table" in cache:
            q, k = self._turn(q, k, cache["pos"])
            kp, vp = _update_paged_cache(cache, k, v)
            out = ops_call("paged_attention", q, kp, vp, cache["table"],
                           cache["pos"], scale=self.scale, window=W)
        elif cache is not None and "pos" in cache:
            q, k = self._turn(q, k, cache["pos"])
            k, v, mask = _update_prealloc_cache(cache, k, v, s, window=W)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=0.0, training=False,
                scale=self.scale)
        elif cache is not None:
            q, k = self._turn(q, k, cache["k"].shape[1])
            k = T.concat([cache["k"], k], axis=1)
            v = T.concat([cache["v"], v], axis=1)
            cache["k"], cache["v"] = k, v
            length = k.shape[1]
            cols = T.arange(length, dtype="int32").unsqueeze(0)
            rows = (length - s + T.arange(s, dtype="int32")).unsqueeze(1)
            mask = cols <= rows
            if W is not None:
                mask = mask & (cols > rows - W)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask.reshape([1, 1, s, length]),
                dropout_p=0.0, training=False, scale=self.scale)
        else:
            q, k = self._turn(q, k, 0)
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=0.0, training=False,
                scale=self.scale, sliding_window=W)
        if self.g_proj is not None:     # one scalar a head
            out = out * F.sigmoid(self.g_proj(x)).unsqueeze(-1)
        return self.o_proj(out.reshape([b, s, -1]))


class LagunaBlock(nn.Layer):
    def __init__(self, cfg: LagunaConfig, layer_idx):
        super().__init__()
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = LagunaAttention(cfg, layer_idx)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.routed = layer_idx not in cfg.mlp_only_layers
        if self.routed:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok,
                scoring="sigmoid", score_bias=False,
                norm_topk=cfg.norm_topk_prob,
                route_scale=cfg.moe_routed_scaling_factor,
                num_shared=cfg.shared_expert_intermediate_size
                // cfg.moe_intermediate_size,
                init_std=cfg.initializer_range, dtype=cfg.dtype,
                held=cfg.held_experts)
        else:
            self.mlp = DeepseekV3MLP(cfg)

    def forward(self, x, cache=None):
        x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        m = self.post_attention_layernorm(x)
        if self.routed and cache is not None and "limit" in cache:
            # a pooled cache says which tokens are real (deepseek.py)
            from .. import tensor_api as T
            at = cache["pos"].astype("int32").unsqueeze(1) \
                + T.arange(x.shape[1], dtype="int32").unsqueeze(0)
            y, cache["expert_load"] = self.mlp(
                m, live=(at < cache["limit"].unsqueeze(1)).reshape([-1]))
            return x + y
        return x + self.mlp(m)


class LagunaModel(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Embedding(cfg)
        self.layers = nn.LayerList(
            [LagunaBlock(cfg, i) for i in range(cfg.num_layers)])
        self.norm = _Norm(cfg, cfg.hidden_size)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        for i, block in enumerate(self.layers):
            x = block(x, cache=caches[i] if caches is not None else None)
        return self.norm(x)


class LagunaForCausalLM(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LagunaModel(cfg)
        self.lm_head = _Proj(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, caches=None):
        return self.lm_head(self.model(input_ids, caches))

    cache_op = "paged_attention"    # the op that reads the block planes

    def cache_planes(self):
        """`k` and `v` per token in every layer, and the KIND of each
        layer's blocks: a window layer's go home behind its band."""
        cfg = self.cfg
        kv = {"k": (cfg.num_kv_heads, cfg.head_dim),
              "v": (cfg.num_kv_heads, cfg.head_dim)}
        return [LayerPlanes(kv, window=cfg.window_of(i))
                for i in range(cfg.num_layers)]

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        from .. import tensor_api as T
        cfg = self.cfg
        length = 0 if max_length is None else max_length
        caches = []
        for _ in range(cfg.num_layers):
            c = {n: T.zeros([batch_size, length, cfg.num_kv_heads,
                             cfg.head_dim], dtype=dtype) for n in ("k", "v")}
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
