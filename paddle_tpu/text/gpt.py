"""GPT model family — the flagship (reference analog: PaddleNLP/PaddleFleetX
GPT-3 implementation driven by Fleet hybrid parallel; config table matches the
reference's gpt2/gpt3 presets).

TPU-native design: Megatron-style tensor parallelism is expressed purely via
parameter PartitionSpecs (ColumnParallel qkv/ffn-in, RowParallel out/ffn-out);
under the fleet engine's pjit step GSPMD inserts the mp collectives.  Long
sequences can route attention through ring_attention (sequence parallel);
blocks can be wrapped in recompute.  Everything is static-shaped for XLA.
"""
from __future__ import annotations

import math

from .. import nn
from ..nn import functional as F
from ..distributed import mesh as mesh_mod
from ..distributed.parallel_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from ..distributed.recompute import recompute
from .decode import _update_prealloc_cache


class GPTConfig:
    PRESETS = {
        "gpt3-125M": dict(hidden_size=768, num_layers=12, num_heads=12),
        "gpt3-350M": dict(hidden_size=1024, num_layers=24, num_heads=16),
        "gpt3-760M": dict(hidden_size=1536, num_layers=24, num_heads=16),
        "gpt3-1.3B": dict(hidden_size=2048, num_layers=24, num_heads=16),
        "gpt3-2.7B": dict(hidden_size=2560, num_layers=32, num_heads=32),
        "gpt3-6.7B": dict(hidden_size=4096, num_layers=32, num_heads=32),
        "gpt3-13B": dict(hidden_size=5120, num_layers=40, num_heads=40),
    }

    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None,
                 max_position_embeddings=2048, hidden_dropout=0.1,
                 attention_dropout=0.1, initializer_range=0.02,
                 use_recompute=False, sequence_parallel=False,
                 context_parallel=False,
                 tensor_parallel=None, num_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, moe_every=1,
                 moe_aux_weight=0.01):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        # sequence_parallel = Megatron-SP: residual stream SEQ-sharded
        # over "mp" between the tp matmuls (reference: fleet's
        # sequence_parallel inside mp groups).  context_parallel = ring
        # attention over the "mp" axis for long sequences (reference:
        # sep_degree / incubate RingFlashAttention).  Orthogonal flags;
        # both may be on.
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel
        # MoE (GShard/Switch style): num_experts > 0 replaces the FFN of
        # every `moe_every`-th block with a routed MoELayer (reference
        # analog: GPT-MoE configs in the incubate moe stack)
        self.num_experts = num_experts
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_every = moe_every
        self.moe_aux_weight = moe_aux_weight
        # default: tensor-parallel layers iff an mp axis exists
        self.tensor_parallel = tensor_parallel if tensor_parallel is not None \
            else mesh_mod.degree("mp") > 1

    @classmethod
    def from_preset(cls, name, **kw):
        return cls(**{**cls.PRESETS[name], **kw})


def _linear(cfg, in_f, out_f, column=True, gather_output=True):
    init = nn.initializer.Normal(0.0, cfg.initializer_range)
    if cfg.tensor_parallel:
        klass = ColumnParallelLinear if column else RowParallelLinear
        l = klass(in_f, out_f, gather_output=gather_output) if column else \
            klass(in_f, out_f)
        init(l.weight)
        return l
    l = nn.Linear(in_f, out_f, weight_attr=init)
    return l


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv_proj = _linear(cfg, cfg.hidden_size, 3 * cfg.hidden_size,
                                column=True)
        self.out_proj = _linear(cfg, cfg.hidden_size, cfg.hidden_size,
                                column=False)
        self.dropout_p = cfg.attention_dropout
        self.context_parallel = cfg.context_parallel
        if self.context_parallel and cfg.attention_dropout > 0:
            # the kv-ring kernel has no dropout support (same as the
            # reference's RingFlashAttention); silently training with
            # different regularization than the config says would be a
            # trap — fail loudly instead
            raise ValueError(
                "context_parallel ring attention does not support "
                "attention_dropout > 0; set attention_dropout=0.0 "
                "(hidden_dropout is unaffected)")

    def forward(self, x, cache=None):
        from .. import tensor_api as T
        b, s, h = x.shape
        qkv = self.qkv_proj(x).reshape([b, s, 3, self.num_heads,
                                        self.head_dim])
        q, k, v = qkv.unbind(axis=2)
        if cache is not None and "table" in cache:
            # block-paged pool (serving engine): write this chunk's k/v
            # through the block table, then attend the whole context via
            # the paged attention op (pallas kernel on TPU, jnp gather
            # fallback elsewhere)
            from .decode import _update_paged_cache
            from ..ops import call as ops_call
            kp, vp = _update_paged_cache(cache, k, v)
            out = ops_call("paged_attention", q, kp, vp, cache["table"],
                           cache["pos"])
        elif cache is not None and "pos" in cache:
            # preallocated cache (jitted decode): static shapes, write at
            # the traced offset, attend under a length mask
            k, v, mask = _update_prealloc_cache(cache, k, v, s)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, training=self.training,
                dropout_p=0.0)
        elif cache is not None:
            k = T.concat([cache["k"], k], axis=1)
            v = T.concat([cache["v"], v], axis=1)
            cache["k"], cache["v"] = k, v
            # decode step: only causal within the concatenated window
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=(s > 1), training=self.training,
                dropout_p=0.0)
        elif self.context_parallel and mesh_mod.degree("mp") > 1:
            from ..distributed.ring_attention import ring_attention
            from ..autograd import engine
            out = engine.apply(
                "ring_attention",
                lambda q_, k_, v_: ring_attention(q_, k_, v_, causal=True),
                [q, k, v])
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout_p,
                training=self.training)
        out = out.reshape([b, s, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc_in = _linear(cfg, cfg.hidden_size, cfg.intermediate_size,
                             column=True)
        self.fc_out = _linear(cfg, cfg.intermediate_size, cfg.hidden_size,
                              column=False)

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig, layer_idx=0):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size)
        use_moe = cfg.num_experts > 0 and \
            (layer_idx + 1) % cfg.moe_every == 0
        if use_moe:
            from ..incubate.nn import MoELayer
            self.mlp = MoELayer(cfg.hidden_size, cfg.intermediate_size,
                                num_experts=cfg.num_experts,
                                top_k=cfg.moe_top_k,
                                capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self.sequence_parallel = cfg.sequence_parallel

    def forward(self, x, cache=None, return_aux=False):
        from ..distributed.parallel_layers import seq_shard
        x = seq_shard(x, self.sequence_parallel, cache)
        x = x + self.dropout(self.attn(self.ln_1(x), cache=cache))
        x = seq_shard(x, self.sequence_parallel, cache)
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        if return_aux:
            # explicit output so the router aux loss crosses recompute's
            # jax.checkpoint boundary instead of leaking via the attribute
            aux = getattr(self.mlp, "aux_loss", None)
            from .. import tensor_api as T
            return x, aux if aux is not None else T.zeros([])
        return x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        if cfg.tensor_parallel:
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                    weight_attr=init)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=init)
        self.drop = nn.Dropout(cfg.hidden_dropout)
        self.h = nn.LayerList([GPTBlock(cfg, layer_idx=i)
                               for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None, caches=None):
        from .. import tensor_api as T
        b, s = input_ids.shape
        if position_ids is None:
            if caches is not None and caches[0] is not None \
                    and "pos" in caches[0]:
                # preallocated cache: offset is a traced scalar, or a [b]
                # vector (per-row decode offsets, batched speculative)
                p = caches[0]["pos"].astype("int32")
                ar = T.arange(0, s, dtype="int32")
                if p.ndim == 0:
                    position_ids = (ar + p).unsqueeze(0)
                else:
                    position_ids = p.unsqueeze(1) + ar.unsqueeze(0)
            else:
                offset = 0
                if caches is not None and caches[0] is not None:
                    offset = caches[0]["k"].shape[1]
                position_ids = T.arange(offset, offset + s, dtype="int64")
                position_ids = position_ids.unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        from ..incubate.nn import MoELayer
        for i, block in enumerate(self.h):
            cache = caches[i] if caches is not None else None
            routed = isinstance(block.mlp, MoELayer)
            if self.cfg.use_recompute and self.training and cache is None:
                if routed:
                    # the aux loss must cross recompute's jax.checkpoint
                    # boundary as an explicit output, then be re-attached
                    # outside it so moe_aux_loss() reads a live tensor
                    x, aux = recompute(block, x, return_aux=True)
                    block.mlp.restore_aux_loss(aux)
                else:
                    x = recompute(block, x)
            else:
                x = block(x, cache=cache)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """LM head ties the (vocab-parallel) embedding weight."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, position_ids=None, caches=None):
        x = self.gpt(input_ids, position_ids, caches)
        # logits = x @ wte.T  (weight tying; mp-sharded vocab under GSPMD)
        logits = x.matmul(self.gpt.wte.weight, transpose_y=True)
        return logits

    # ------------------------------------------------- pipeline parallelism
    def pipeline_decompose(self):
        """Stage plan for the fleet engine's pp path (reference analog:
        PipelineLayer's LayerDesc segmentation in pp_layers.py).  The
        homogeneous transformer blocks are pipelined; embedding and the
        ln_f+tied-head stay outside under plain GSPMD (first/last-stage
        layers in the reference)."""
        return {
            "blocks": list(self.gpt.h),
            "pre": self._pp_pre,
            "post": self._pp_post,
            "remat": self.cfg.use_recompute,
        }

    def _pp_pre(self, input_ids):
        from .. import tensor_api as T
        b, s = input_ids.shape
        position_ids = T.arange(0, s, dtype="int32").unsqueeze(0)
        x = self.gpt.wte(input_ids) + self.gpt.wpe(position_ids)
        return self.gpt.drop(x)

    def _pp_post(self, x):
        x = self.gpt.ln_f(x)
        return x.matmul(self.gpt.wte.weight, transpose_y=True)

    cache_op = "paged_attention"            # the op that reads the planes

    def cache_planes(self):
        from .decode import kv_cache_planes
        return kv_cache_planes(self.cfg)

    def new_caches(self, batch_size, dtype="float32", max_length=None):
        """Concat-style caches (eager decode) or, with `max_length`, the
        preallocated static-shape caches the jitted decode loop uses."""
        from .. import tensor_api as T
        hd = self.cfg.hidden_size // self.cfg.num_heads
        L = 0 if max_length is None else max_length
        caches = []
        for _ in range(self.cfg.num_layers):
            c = {"k": T.zeros([batch_size, L, self.cfg.num_heads, hd],
                              dtype=dtype),
                 "v": T.zeros([batch_size, L, self.cfg.num_heads, hd],
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


class GPTPretrainingCriterion(nn.Layer):
    def forward(self, logits, labels, loss_mask=None):
        loss = F.cross_entropy(logits, labels, reduction="none")
        if loss_mask is not None:
            m = loss_mask.astype(loss.dtype)
            return (loss * m).sum() / m.sum().clip(min=1.0)
        return loss.mean()


def gpt_loss_fn(model, input_ids, labels):
    """Canonical pretrain loss for TrainStep/fleet engine (adds the MoE
    load-balancing aux loss when the config routes any block)."""
    logits = model(input_ids)
    loss = F.cross_entropy(logits, labels, reduction="mean")
    cfg = getattr(model, "cfg", None)
    if cfg is not None and getattr(cfg, "num_experts", 0):
        from ..incubate.nn import moe_aux_loss
        aux = moe_aux_loss(model)
        if aux is not None:
            loss = loss + cfg.moe_aux_weight * aux
    return loss
