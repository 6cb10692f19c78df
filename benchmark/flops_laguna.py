"""Operations and bytes that a decoder of two KINDS of attention layer
needs -- full layers and window layers with head counts of their own over
shared kv heads, one scalar gate a head, a leading dense SwiGLU and then
ONE CHIP'S SHARE of a routed expert layer -- from a configuration's
shapes alone.  Keys are those of the published config.json (hidden_size,
num_key_value_heads, head_dim, layer_types,
num_attention_heads_per_layer, sliding_window, mlp_only_layers,
intermediate_size, moe_intermediate_size,
shared_expert_intermediate_size, num_experts_per_tok, vocab_size,
num_hidden_layers) with ``num_experts`` the experts HELD here and
``router_experts`` the router's outputs.

`benchmark/flops.py`, `flops_moe_mla.py` and `flops_hybrid.py` must not
be used for such a configuration: they take one head count for every
layer and multiply K/V by layers that here differ in what they read.
"""
FULL, WINDOW = "full_attention", "sliding_attention"


def _dims(cfg):
    out = {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_key_value_heads",
        "head_dim", "vocab_size", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts", "num_experts_per_tok", "sliding_window")}
    out["router"] = int(cfg.get("router_experts", cfg["num_experts"]))
    return out


def layers(cfg):
    return range(int(cfg["num_hidden_layers"]))


def kind_of(cfg, i):
    return cfg["layer_types"][i]


def layers_of(cfg, kind):
    """The layers of `kind` in the cut."""
    return [i for i in layers(cfg) if kind_of(cfg, i) == kind]


def heads_of(cfg, i):
    return int(cfg["num_attention_heads_per_layer"][i])


def routed(cfg, i):
    return i not in [int(n) for n in cfg["mlp_only_layers"]]


def kv_proj_params(cfg):
    d = _dims(cfg)
    return 2 * d["hidden_size"] * d["num_key_value_heads"] * d["head_dim"]


def attn_params(cfg, i):
    """Matmul weights of layer i's attention: W_q and W_o by its own head
    count, W_k, W_v, and the gate's one column a head."""
    d = _dims(cfg)
    h, heads = d["hidden_size"], heads_of(cfg, i)
    return 2 * h * heads * d["head_dim"] + kv_proj_params(cfg) + h * heads


def dense_params(cfg):
    d = _dims(cfg)
    return 3 * d["hidden_size"] * d["intermediate_size"]


def expert_params(cfg):
    """One routed expert: three matrices."""
    d = _dims(cfg)
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def every_token_expert_params(cfg):
    """Of an expert layer, what every token multiplies whatever its
    routing: the router over all experts and the shared expert."""
    d = _dims(cfg)
    return d["hidden_size"] * d["router"] \
        + 3 * d["hidden_size"] * d["shared_expert_intermediate_size"]


def ffn_token_params(cfg, i):
    """What every token multiplies in layer i's FFN outside the routed
    experts."""
    return every_token_expert_params(cfg) if routed(cfg, i) \
        else dense_params(cfg)


def head_params(cfg):
    d = _dims(cfg)
    return d["hidden_size"] * d["vocab_size"]


def held_params(cfg):
    """Every parameter this chip holds in matrices: attention, the dense
    layer, routers, shared and HELD routed experts, embedding and head."""
    d = _dims(cfg)
    return (sum(attn_params(cfg, i) + ffn_token_params(cfg, i)
                + (d["num_experts"] * expert_params(cfg)
                   if routed(cfg, i) else 0) for i in layers(cfg))
            + 2 * head_params(cfg))


def decode_token_params(cfg):
    """Matmul weights a DECODED token multiplies outside the routed
    experts: every layer's attention, the dense layer, every router and
    shared expert, the head."""
    return sum(attn_params(cfg, i) + ffn_token_params(cfg, i)
               for i in layers(cfg)) + head_params(cfg)


def prefill_token_params(cfg):
    """The same for a PROMPT token.  A chunk returns no logits: no head,
    and of its LAST layer only what the cache needs, the K and V
    projections."""
    last = int(cfg["num_hidden_layers"]) - 1
    return (decode_token_params(cfg) - head_params(cfg)
            - attn_params(cfg, last) + kv_proj_params(cfg)
            - ffn_token_params(cfg, last))


def prefill_routed_layers(cfg):
    """Expert layers that run in a chunk (the last layer's is dead)."""
    return sum(routed(cfg, i) for i in layers(cfg)[:-1])


def pair_flops(cfg, kind, chunk=False):
    """FLOPs of one (query, visible position) pair summed over the layers
    of `kind`: the score and the weighted sum, every query head of each.
    `chunk`: in a prefill program, whose last layer attends nothing."""
    d = _dims(cfg)
    last = int(cfg["num_hidden_layers"]) - 1
    return sum(4.0 * heads_of(cfg, i) * d["head_dim"]
               for i in layers_of(cfg, kind) if not (chunk and i == last))


def visible_pairs(tokens, ctx, window=None):
    """(query, visible position) pairs of a chunk of `tokens` that starts
    at context `ctx`: token i sees ctx + i + 1 positions, under a
    `window` at most that many."""
    if window is None:
        return tokens * ctx + tokens * (tokens + 1) / 2.0
    return float(sum(min(ctx + i + 1, window) for i in range(int(tokens))))


def serve_flops(cfg, prefilled, decoded, chunk_pairs, decode_pairs,
                local_assignments):
    """Model FLOPs of a serving engine's work on this share: 2 per matmul
    weight a token REALLY multiplies (`local_assignments`: the
    assignments that fell on the held experts, decode and prefill, as the
    programs count them -- not `num_experts_per_tok` a token) and
    attention by kind: `chunk_pairs` and `decode_pairs` are {kind: pairs a
    layer of that kind attends}, the window kind's bounded by the window."""
    return (2.0 * prefill_token_params(cfg) * prefilled
            + 2.0 * decode_token_params(cfg) * decoded
            + 2.0 * expert_params(cfg) * local_assignments
            + sum(pair_flops(cfg, k, chunk=True) * chunk_pairs[k]
                  + pair_flops(cfg, k) * decode_pairs[k]
                  for k in (FULL, WINDOW)))


def paged_work(cfg, kind, blocks, rows, block_size, itemsize=2):
    """(flops, bytes) of a decode step's paged attention in the layers of
    `kind`: K and V of `blocks` pool blocks a layer (the blocks a sound
    walk reads: a full layer's live blocks, a window layer's band), q
    read and o written for `rows` rows, and the pairs' FLOPs by the
    layers' own head counts."""
    d = _dims(cfg)
    n = len(layers_of(cfg, kind))
    positions = float(blocks) * block_size
    kv = positions * d["num_key_value_heads"] * d["head_dim"] * 2 * itemsize
    qo = sum(2.0 * rows * heads_of(cfg, i) * d["head_dim"] * itemsize
             for i in layers_of(cfg, kind))
    return pair_flops(cfg, kind) * positions, kv * n + qo


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


def kv_bytes_per_token(cfg, kind, itemsize=2):
    """Bytes one cached position takes over the layers of `kind`."""
    d = _dims(cfg)
    return len(layers_of(cfg, kind)) * 2 * d["num_key_value_heads"] \
        * d["head_dim"] * itemsize
