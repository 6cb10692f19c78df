"""Operations and bytes that a hybrid decoder needs -- a period of GQA
layers and gated delta-rule (KDA) layers, every layer followed by ONE
CHIP'S SHARE of a routed expert layer -- from a configuration's shapes
alone.  Keys are those of the published config.json (hidden_size,
num_attention_heads, num_key_value_heads, head_dim, linear_attn_config,
gqa_layers, moe_intermediate_size, n_shared_experts, num_experts_per_tok,
vocab_size, num_hidden_layers) with ``n_routed_experts`` the experts HELD
here, ``router_experts`` the router's outputs and ``kda_gate_rank`` the
rank of the KDA layer's gate pairs.

`benchmark/flops.py` and `benchmark/flops_moe_mla.py` must not be used
for such a configuration: the first multiplies K/V by every layer (here
one layer in four has any), the second wants a latent rank.
"""


def _dims(cfg):
    lin = cfg["linear_attn_config"]
    out = {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size",
        "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "kda_gate_rank")}
    out.update(router=int(cfg.get("router_experts",
                                  cfg["n_routed_experts"])),
               kda_heads=int(lin["num_heads"]), kda_d=int(lin["head_dim"]),
               taps=int(lin["short_conv_kernel_size"]))
    return out


def gqa_layers(cfg):
    """The GQA layers of the cut: those of `gqa_layers` under the depth."""
    return [int(i) for i in cfg["gqa_layers"]
            if int(i) < int(cfg["num_hidden_layers"])]


def kda_layers(cfg):
    return int(cfg["num_hidden_layers"]) - len(gqa_layers(cfg))


def gqa_params(cfg):
    """Matmul weights of a GQA layer's mixer: W_q, W_k, W_v, W_o and the
    elementwise gate's W_g."""
    d = _dims(cfg)
    h, q = d["hidden_size"], d["num_attention_heads"] * d["head_dim"]
    kv = d["num_key_value_heads"] * d["head_dim"]
    return h * q + 2 * h * kv + q * h + h * q


def kda_out_params(cfg):
    """Of a KDA mixer, what follows the recurrence: W_o and the output
    gate's pair."""
    d = _dims(cfg)
    h, w, r = d["hidden_size"], d["kda_heads"] * d["kda_d"], \
        d["kda_gate_rank"]
    return w * h + h * r + r * w


def kda_params(cfg):
    """Weights of a KDA layer's mixer that a token multiplies: q, k, v,
    W_o, the decay's and the gate's pairs, beta, and the convolution's
    taps (2 FLOPs a tap a channel a token, like a weight)."""
    d = _dims(cfg)
    h, w, r = d["hidden_size"], d["kda_heads"] * d["kda_d"], \
        d["kda_gate_rank"]
    return (3 * h * w + h * r + r * w + h * d["kda_heads"]
            + 3 * w * d["taps"] + kda_out_params(cfg))


def expert_params(cfg):
    """One expert, routed or shared: three matrices."""
    d = _dims(cfg)
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def every_token_expert_params(cfg):
    """Of an expert layer, what every token multiplies whatever its
    routing: the router over all experts and the shared experts."""
    d = _dims(cfg)
    return d["hidden_size"] * d["router"] \
        + d["n_shared_experts"] * expert_params(cfg)


def head_params(cfg):
    d = _dims(cfg)
    return d["hidden_size"] * d["vocab_size"]


def held_params(cfg):
    """Every parameter this chip holds in matrices: mixers, routers,
    shared and HELD routed experts, embedding and head."""
    d = _dims(cfg)
    per_layer = every_token_expert_params(cfg) \
        + d["n_routed_experts"] * expert_params(cfg)
    return (len(gqa_layers(cfg)) * gqa_params(cfg)
            + kda_layers(cfg) * kda_params(cfg)
            + d["num_hidden_layers"] * per_layer + 2 * head_params(cfg))


def kda_state_flops_per_token(cfg):
    """FLOPs of one position of the recurrence in ONE KDA layer, every
    head: the decay (1 a state element), S^T k, the rank-one update and
    S^T q (2 each)."""
    d = _dims(cfg)
    return 7.0 * d["kda_heads"] * d["kda_d"] * d["kda_d"]


def attention_flops_per_pair(cfg):
    """FLOPs of one (query, visible position) pair in ONE GQA layer:
    the score and the weighted sum, every query head."""
    d = _dims(cfg)
    return 4.0 * d["num_attention_heads"] * d["head_dim"]


def visible_pairs(tokens, ctx):
    """(query, visible position) pairs of a chunk of `tokens` that starts
    at context `ctx`: token i sees ctx + i + 1 positions."""
    return tokens * ctx + tokens * (tokens + 1) / 2.0


def decode_token_params(cfg):
    """Matmul weights a DECODED token multiplies outside the routed
    experts: every mixer, every router and shared expert, the head."""
    d = _dims(cfg)
    return (len(gqa_layers(cfg)) * gqa_params(cfg)
            + kda_layers(cfg) * kda_params(cfg)
            + d["num_hidden_layers"] * every_token_expert_params(cfg)
            + head_params(cfg))


def last_is_kda(cfg):
    return int(cfg["num_hidden_layers"]) - 1 not in gqa_layers(cfg)


def prefill_token_params(cfg):
    """The same for a PROMPT token.  A chunk returns no logits: no head,
    and of its LAST layer only what the cache needs -- the K and V
    projections of a GQA layer, or a KDA layer's mixer up to the
    recurrence (the state has to be written), and no expert layer."""
    d = _dims(cfg)
    h = d["hidden_size"]
    if last_is_kda(cfg):
        kept = kda_params(cfg) - kda_out_params(cfg)
        dropped = kda_params(cfg)
    else:
        kept = 2 * h * d["num_key_value_heads"] * d["head_dim"]
        dropped = gqa_params(cfg)
    return (decode_token_params(cfg) - head_params(cfg) - dropped + kept
            - every_token_expert_params(cfg))


def serve_flops(cfg, prefilled, decoded, prefill_pairs, decode_pairs,
                local_assignments):
    """Model FLOPs of a serving engine's work on this share: 2 per matmul
    weight a token REALLY multiplies (`local_assignments`: the
    assignments that fell on the held experts, decode and prefill, as the
    programs count them -- not `num_experts_per_tok` a token), the KDA
    layers' state FLOPs a token, and attention by context in the GQA
    layers only (a chunk's last layer, were it a GQA layer, attends
    nothing: dead code in a prefill program)."""
    gqa, kda = len(gqa_layers(cfg)), kda_layers(cfg)
    chunk_gqa = gqa if last_is_kda(cfg) else gqa - 1
    return (2.0 * prefill_token_params(cfg) * prefilled
            + 2.0 * decode_token_params(cfg) * decoded
            + 2.0 * expert_params(cfg) * local_assignments
            + kda_state_flops_per_token(cfg) * kda * (prefilled + decoded)
            + attention_flops_per_pair(cfg)
            * (chunk_gqa * prefill_pairs + gqa * decode_pairs))


def kda_step_work(cfg, rows):
    """(flops, bytes) of the decode step's recurrence for `rows` live
    rows, all KDA layers: the float32 state read and written once, and
    the vectors beside it (decay, k, q, v, beta broadcast, o: float32)."""
    d = _dims(cfg)
    heads, dk = d["kda_heads"], d["kda_d"]
    per_row = 2 * heads * dk * dk * 4 + 6 * heads * dk * 4
    return (kda_state_flops_per_token(cfg) * kda_layers(cfg) * rows,
            float(per_row) * kda_layers(cfg) * rows)


def held_expert_work(cfg, assignments, touched, itemsize=2):
    """(flops, bytes) of the grouped products of the held experts:
    2 FLOPs a weight of one expert an assignment; the weights of the
    experts TOUCHED (summed over layers and steps) and the rows an
    assignment reads and writes in the three products."""
    d = _dims(cfg)
    rows = 3 * (d["hidden_size"] + d["moe_intermediate_size"])
    return (2.0 * expert_params(cfg) * assignments,
            float(touched) * expert_params(cfg) * itemsize
            + float(assignments) * rows * itemsize)


def gqa_decode_bytes(cfg, context_sum, itemsize=2):
    """Bytes of K and V that decoding needs in the GQA layers of the cut
    (one in four, NOT `num_hidden_layers`): the decoded tokens' summed
    context x kv heads x d x 2."""
    d = _dims(cfg)
    return (float(context_sum) * d["num_key_value_heads"] * d["head_dim"]
            * 2 * itemsize * len(gqa_layers(cfg)))


def state_bytes_per_row(cfg, itemsize=2):
    """Bytes of one request's recurrent state over the KDA layers: the
    float32 state and the convolution's tail."""
    d = _dims(cfg)
    w = d["kda_heads"] * d["kda_d"]
    return kda_layers(cfg) * (d["kda_heads"] * d["kda_d"] ** 2 * 4
                              + (d["taps"] - 1) * 3 * w * itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    d = _dims(cfg)
    return len(gqa_layers(cfg)) * 2 * d["num_key_value_heads"] \
        * d["head_dim"] * itemsize
