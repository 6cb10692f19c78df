"""Operations and bytes that a latent-attention, routed-expert decoder
needs, from a configuration's shapes alone (the keys of the published
config.json: kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, moe_intermediate_size, n_routed_experts, n_shared_experts,
num_experts_per_tok, first_k_dense_replace).  `benchmark/flops.py`
counts a dense model from `intermediate_size` and must not be used for
such a configuration.
"""


def _dims(cfg):
    return {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "intermediate_size", "vocab_size", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "first_k_dense_replace")}


def moe_layers(cfg):
    d = _dims(cfg)
    return d["num_hidden_layers"] - d["first_k_dense_replace"]


def attention_params(cfg):
    """Matmul weights of one layer's attention: W_q, W_kva, W_kvb, W_o."""
    d = _dims(cfg)
    h, heads = d["hidden_size"], d["num_attention_heads"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    return (h * heads * qk + h * (d["kv_lora_rank"] + d["qk_rope_head_dim"])
            + d["kv_lora_rank"] * heads
            * (d["qk_nope_head_dim"] + d["v_head_dim"])
            + heads * d["v_head_dim"] * h)


def expert_params(cfg):
    """One routed expert: three matrices of hidden x expert width."""
    d = _dims(cfg)
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def layer_params(cfg, routed):
    """Matmul weights HELD by one layer: attention and the dense
    feed-forward, or attention, router, every routed expert and the
    shared experts."""
    d = _dims(cfg)
    if not routed:
        return attention_params(cfg) \
            + 3 * d["hidden_size"] * d["intermediate_size"]
    return (attention_params(cfg)
            + d["hidden_size"] * d["n_routed_experts"]
            + d["n_routed_experts"] * expert_params(cfg)
            + d["n_shared_experts"] * expert_params(cfg))


def active_layer_params(cfg, routed):
    """Matmul weights one token MULTIPLIES in a layer: of the routed
    experts only the `num_experts_per_tok` it is sent to."""
    d = _dims(cfg)
    if not routed:
        return layer_params(cfg, False)
    return (attention_params(cfg)
            + d["hidden_size"] * d["n_routed_experts"]
            + (d["num_experts_per_tok"] + d["n_shared_experts"])
            * expert_params(cfg))


def active_body_params(cfg):
    """Active matmul weights of all layers, without the head."""
    d = _dims(cfg)
    return (d["first_k_dense_replace"] * active_layer_params(cfg, False)
            + moe_layers(cfg) * active_layer_params(cfg, True))


def head_params(cfg):
    d = _dims(cfg)
    return d["hidden_size"] * d["vocab_size"]


def attention_flops_per_pair(cfg):
    """FLOPs of one (query token, visible position) pair in ONE layer in
    the expanded form, the lesser of the two forms: scores over
    qk_nope + qk_rope and the weighted sum over v, every head."""
    d = _dims(cfg)
    return 2.0 * d["num_attention_heads"] * (
        d["qk_nope_head_dim"] + d["qk_rope_head_dim"] + d["v_head_dim"])


def visible_pairs(tokens, ctx):
    """(query, visible position) pairs of a chunk of `tokens` that starts
    at context `ctx`: token i sees ctx + i + 1 positions."""
    return tokens * ctx + tokens * (tokens + 1) / 2.0


def prefill_body_params(cfg):
    """Active matmul weights a PROMPT token has to multiply: every layer
    but the last, and of the last the projection of the row it caches.
    The first token comes from a decode step, so a chunk returns no
    logits and the output of its last layer is needed by nothing (and is
    dead code in the prefill program)."""
    d = _dims(cfg)
    last_routed = d["num_hidden_layers"] > d["first_k_dense_replace"]
    return (active_body_params(cfg) - active_layer_params(cfg, last_routed)
            + d["hidden_size"] * (d["kv_lora_rank"] + d["qk_rope_head_dim"]))


def serve_flops(cfg, prefilled, decoded, prefill_pairs, decode_pairs):
    """Model FLOPs of a serving engine's work: 2 per active matmul weight
    per token pushed through the layers (a prompt token: all but the last
    layer, `prefill_body_params`), the head for DECODED tokens only, and
    attention by context over the (query, visible position) pairs: a
    chunk's in all layers but the last, a decode step's in all."""
    layers = _dims(cfg)["num_hidden_layers"]
    return (2.0 * prefill_body_params(cfg) * prefilled
            + 2.0 * (active_body_params(cfg) + head_params(cfg)) * decoded
            + attention_flops_per_pair(cfg)
            * ((layers - 1) * prefill_pairs + layers * decode_pairs))


def latent_bytes_per_token(cfg, itemsize=2):
    """Bytes of the cached latent row [c | k_rope] of one token in one
    layer."""
    d = _dims(cfg)
    return (d["kv_lora_rank"] + d["qk_rope_head_dim"]) * itemsize


def latent_flops_per_cached_token(cfg):
    """FLOPs the absorbed decode form spends on one cached token in one
    layer: scores over [c | k_rope], the weighted sum over c, every
    head."""
    d = _dims(cfg)
    return 2.0 * d["num_attention_heads"] * (
        2 * d["kv_lora_rank"] + d["qk_rope_head_dim"])


def latent_decode_work(cfg, context_sum, itemsize=2):
    """(flops, bytes) of decode attention over `context_sum` cached
    tokens (summed over the decoded tokens), all layers."""
    layers = _dims(cfg)["num_hidden_layers"]
    return (latent_flops_per_cached_token(cfg) * context_sum * layers,
            latent_bytes_per_token(cfg, itemsize) * context_sum * layers)


def touched_expert_bytes(cfg, touched, itemsize=2):
    """Bytes of the weights of `touched` routed experts (summed over the
    expert layers of a step)."""
    return float(touched) * expert_params(cfg) * itemsize
