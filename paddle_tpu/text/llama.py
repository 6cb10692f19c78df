"""LLaMA family (reference analog: PaddleNLP transformers/llama — the
hybrid-parallel mp+pp+sharding+recompute benchmark model).

RoPE, RMSNorm, SwiGLU, GQA; tensor-parallel via PartitionSpec-annotated
projections like GPT.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..autograd import engine
from ..nn import functional as F
from ..distributed import mesh as mesh_mod
from ..distributed.parallel_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from ..distributed.recompute import recompute


class LlamaConfig:
    PRESETS = {
        "llama-7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                         intermediate_size=11008),
        "llama-13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                          intermediate_size=13824),
        "llama-tiny": dict(hidden_size=256, num_layers=2, num_heads=4,
                           intermediate_size=688),
        # Mistral = the llama block + GQA(8 kv) + sliding-window 4096
        # (identical weight layout, so convert_hf_llama loads it)
        "mistral-7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                           num_kv_heads=8, intermediate_size=14336,
                           vocab_size=32000, rope_theta=10000.0,
                           max_position_embeddings=32768,
                           sliding_window=4096),
    }

    def __init__(self, vocab_size=32000, hidden_size=4096, num_layers=32,
                 num_heads=32, num_kv_heads=None, intermediate_size=11008,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, initializer_range=0.02,
                 use_recompute=False, sequence_parallel=False,
                 context_parallel=False, tensor_parallel=None,
                 attention_bias=False, sliding_window=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        # sequence_parallel = Megatron-SP residual seq-sharding;
        # context_parallel = ring attention over "mp" (GQA-native ring —
        # unrepeated kv shards rotate).  See GPTConfig for the mapping to
        # the reference's fleet sequence_parallel / RingFlashAttention.
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel
        self.tensor_parallel = tensor_parallel if tensor_parallel is not None \
            else mesh_mod.degree("mp") > 1
        # attention_bias: q/k/v projections carry bias (Qwen2-style).
        # sliding_window: Mistral-style banded causal attention — the
        # pallas kernel skips KV blocks left of the band, so long-context
        # compute scales with window*L, not L^2.
        self.attention_bias = attention_bias
        self.sliding_window = sliding_window
        if sliding_window and context_parallel:
            raise ValueError(
                "sliding_window does not compose with context_parallel "
                "(the ring rotates full KV shards); pick one")

    @classmethod
    def from_preset(cls, name, **kw):
        return cls(**{**cls.PRESETS[name], **kw})


def _rope(q, k, positions, theta):
    """Rotary embedding applied to [b, s, h, d] arrays (pure jax)."""
    d = q.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = positions[..., None].astype(jnp.float32) * inv  # [b?, s, d/2]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., ::2], x[..., 1::2]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)

    return rot(q), rot(k)


def _tp_linear(cfg, in_f, out_f, column=True, bias=False):
    init = nn.initializer.Normal(0.0, cfg.initializer_range)
    if cfg.tensor_parallel:
        l = (ColumnParallelLinear if column else RowParallelLinear)(
            in_f, out_f, has_bias=bias)
        init(l.weight)
        return l
    return nn.Linear(in_f, out_f, weight_attr=init,
                     bias_attr=None if bias else False)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.q_proj = _tp_linear(cfg, cfg.hidden_size,
                                 cfg.num_heads * self.head_dim,
                                 bias=cfg.attention_bias)
        self.k_proj = _tp_linear(cfg, cfg.hidden_size,
                                 cfg.num_kv_heads * self.head_dim,
                                 bias=cfg.attention_bias)
        self.v_proj = _tp_linear(cfg, cfg.hidden_size,
                                 cfg.num_kv_heads * self.head_dim,
                                 bias=cfg.attention_bias)
        self.o_proj = _tp_linear(cfg, cfg.num_heads * self.head_dim,
                                 cfg.hidden_size, column=False)

    def forward(self, x, cache=None):
        from .. import tensor_api as T
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, self.head_dim])

        prealloc = cache is not None and "pos" in cache
        if prealloc:
            def rope_fn(qa, ka, pa, theta=cfg.rope_theta):
                # pa: scalar offset, or [b] per-row offsets (batched
                # speculative decode) -> positions [1|b, s]
                base = jnp.atleast_1d(pa.astype(jnp.int32))
                pos = base[:, None] + jnp.arange(qa.shape[1])[None, :]
                return _rope(qa, ka, pos, theta)
            q, k = engine.apply("rope", rope_fn, [q, k, cache["pos"]])
        else:
            offset = 0
            if cache is not None:
                offset = cache["k"].shape[1]

            def rope_fn(qa, ka, offset, theta):
                pos = (offset + jnp.arange(qa.shape[1]))[None, :]
                return _rope(qa, ka, pos, theta)

            # offset/theta ride in consts so graph capture (onnx export)
            # can rebuild the rotation tables
            q, k = engine.apply("rope", rope_fn, [q, k],
                                {"offset": offset,
                                 "theta": cfg.rope_theta})

        mask = None
        W = cfg.sliding_window
        paged = cache is not None and "table" in cache
        if paged:
            # block-paged pool (serving engine): write-then-attend via
            # the paged attention op; GQA kv heads stay unrepeated (the
            # pallas kernel groups via its kv index map, the fallback
            # repeats inside sdpa_k).  A sliding window is the op's band:
            # `cache_planes()` names it, and the pool takes back the
            # blocks behind it
            from .decode import _update_paged_cache
            from ..ops import call as ops_call
            kp, vp = _update_paged_cache(cache, k, v)
            out = ops_call("paged_attention", q, kp, vp, cache["table"],
                           cache["pos"], window=W or None)
            return self.o_proj(out.reshape([b, s, -1]))
        if prealloc:
            from .decode import _update_prealloc_cache
            k, v, mask = _update_prealloc_cache(cache, k, v, s, window=W)
        elif cache is not None:
            k = T.concat([cache["k"], k], axis=1)
            v = T.concat([cache["v"], v], axis=1)
            cache["k"], cache["v"] = k, v
            if W:
                # banded mask over the concatenated window (row r sits at
                # absolute position Lk - s + r; attends cols in
                # (abs_r - W, abs_r])
                Lk = k.shape[1]
                cols = T.arange(Lk, dtype="int32").unsqueeze(0)
                rows = (Lk - s
                        + T.arange(s, dtype="int32")).unsqueeze(1)
                mask = ((cols <= rows)
                        & (cols > rows - W)).reshape([1, 1, s, Lk])
        # GQA heads stay UNREPEATED: the sdpa dispatch handles grouping —
        # natively inside the pallas flash kernel (kv-head index map), or
        # via repeat_interleave in the XLA fallback (sdpa_k)
        if prealloc or mask is not None:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=0.0,
                training=self.training)
        elif (cache is None and cfg.context_parallel
              and mesh_mod.degree("mp") > 1):
            from ..distributed.ring_attention import ring_attention
            out = engine.apply(
                "ring_attention",
                lambda q_, k_, v_: ring_attention(q_, k_, v_, causal=True),
                [q, k, v])
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=(cache is None or s > 1), dropout_p=0.0,
                training=self.training,
                sliding_window=W if cache is None else None)
        return self.o_proj(out.reshape([b, s, -1]))


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = _tp_linear(cfg, cfg.hidden_size,
                                    cfg.intermediate_size)
        self.up_proj = _tp_linear(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.down_proj = _tp_linear(cfg, cfg.intermediate_size,
                                    cfg.hidden_size, column=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self.sequence_parallel = cfg.sequence_parallel

    def forward(self, x, cache=None):
        from ..distributed.parallel_layers import seq_shard
        x = seq_shard(x, self.sequence_parallel, cache)
        x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        x = seq_shard(x, self.sequence_parallel, cache)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        if cfg.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                             weight_attr=init)
        self.layers = nn.LayerList(
            [LlamaBlock(cfg) for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        x = self.embed_tokens(input_ids)
        for i, block in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            if self.cfg.use_recompute and self.training and cache is None:
                x = recompute(block, x)
            else:
                x = block(x, cache=cache)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.llama = LlamaModel(cfg)
        self.lm_head = _tp_linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, caches=None):
        x = self.llama(input_ids, caches)
        return self.lm_head(x)

    cache_op = "paged_attention"            # the op that reads the planes

    def cache_planes(self):
        """`k` and `v` per token a layer; under a sliding window every
        layer is of the window kind, one block group whose blocks go
        home behind the band."""
        from .decode import LayerPlanes, kv_cache_planes
        planes = kv_cache_planes(self.cfg)
        if self.cfg.sliding_window:
            planes = [LayerPlanes(p, window=self.cfg.sliding_window)
                      for p in planes]
        return planes

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        from .. import tensor_api as T
        hd = self.cfg.hidden_size // self.cfg.num_heads
        L = 0 if max_length is None else max_length
        caches = []
        for _ in range(self.cfg.num_layers):
            c = {"k": T.zeros([batch_size, L, self.cfg.num_kv_heads, hd],
                              dtype=dtype),
                 "v": T.zeros([batch_size, L, self.cfg.num_kv_heads, hd],
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
