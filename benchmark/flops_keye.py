"""Operations and bytes of a GQA decoder whose queries attend only the
positions a learned indexer picks (DeepSeek-V3.2's sparse attention:
Keye-VL-2.0's language model), from a configuration's shapes alone.  Keys
are those of the published config.json (hidden_size, num_attention_heads,
num_key_value_heads, head_dim, num_experts, num_experts_per_tok,
moe_intermediate_size, vocab_size, num_hidden_layers) and its
``sa_config`` (indexer_num_heads, indexer_head_dim, topk).

Every count is of the MODEL's work, whatever form serves it: an indexer
key of `indexer_head_dim` values (the pool pads it to 128 lanes), a
scored pair is ``2 x heads x indexer_head_dim`` FLOPs, a selected pair
``4 x heads x head_dim`` (the score and the weighted sum of every query
head), and a pair the prefill kernel computes beyond the selection is no
work of the model.
"""


def _dims(cfg):
    sa = cfg["sa_config"]
    out = {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")}
    out.update(index_heads=int(sa["indexer_num_heads"]),
               index_dim=int(sa["indexer_head_dim"]), topk=int(sa["topk"]))
    return out


def kv_proj_params(cfg):
    """W_k and W_v."""
    d = _dims(cfg)
    return 2 * d["hidden_size"] * d["num_key_value_heads"] * d["head_dim"]


def index_key_params(cfg):
    """W_kI: the indexer's key, cached a position."""
    d = _dims(cfg)
    return d["hidden_size"] * d["index_dim"]


def attn_params(cfg):
    """Matmul weights of one layer's attention: W_q, W_k, W_v, W_o and
    the indexer's W_qI, W_kI and head weights W_w."""
    d = _dims(cfg)
    h, heads = d["hidden_size"], d["num_attention_heads"]
    return (2 * h * heads * d["head_dim"] + kv_proj_params(cfg)
            + h * d["index_heads"] * (d["index_dim"] + 1)
            + index_key_params(cfg))


def router_params(cfg):
    d = _dims(cfg)
    return d["hidden_size"] * d["num_experts"]


def expert_params(cfg):
    """One routed expert: three matrices."""
    d = _dims(cfg)
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def head_params(cfg):
    d = _dims(cfg)
    return d["hidden_size"] * d["vocab_size"]


def held_params(cfg):
    """Every parameter this chip holds: every layer's attention, indexer,
    router and experts, its norms, the embedding and the head."""
    d = _dims(cfg)
    h, dh = d["hidden_size"], d["head_dim"]
    norms = 2 * h + 2 * dh + 2 * d["index_dim"]
    layer = attn_params(cfg) + router_params(cfg) \
        + d["num_experts"] * expert_params(cfg) + norms
    return d["num_hidden_layers"] * layer + 2 * head_params(cfg) + h


def decode_token_params(cfg):
    """Matmul weights a DECODED token multiplies outside the routed
    experts: every layer's attention, indexer and router, the head."""
    d = _dims(cfg)
    return d["num_hidden_layers"] * (attn_params(cfg) + router_params(cfg)) \
        + head_params(cfg)


def prefill_token_params(cfg):
    """The same for a PROMPT token.  A chunk returns no logits: no head,
    and of its LAST layer only what the cache needs, the K, V and indexer
    key projections."""
    return (decode_token_params(cfg) - head_params(cfg) - attn_params(cfg)
            - router_params(cfg) + kv_proj_params(cfg)
            + index_key_params(cfg))


def scored_flops(cfg):
    """FLOPs of one scored (query, position) pair in one layer:
    ``w . relu(q^I . k^I)`` over the indexer heads."""
    d = _dims(cfg)
    return 2.0 * d["index_heads"] * d["index_dim"]


def selected_flops(cfg):
    """FLOPs of one selected (query, position) pair in one layer: the
    score and the weighted sum of every query head."""
    d = _dims(cfg)
    return 4.0 * d["num_attention_heads"] * d["head_dim"]


def serve_flops(cfg, prefilled, decoded, assignments, decode_scored,
                decode_selected, chunk_scored, chunk_selected):
    """Model FLOPs of a serving engine's work: 2 per matmul weight a token
    REALLY multiplies (`assignments`: the expert assignments decode and
    prefill, as the programs count them) and the pairs: a decode step's
    scored and selected positions in every layer, a chunk's in every
    layer but the last (pruned: no logits)."""
    layers = int(cfg["num_hidden_layers"])
    return (2.0 * prefill_token_params(cfg) * prefilled
            + 2.0 * decode_token_params(cfg) * decoded
            + 2.0 * expert_params(cfg) * assignments
            + layers * (scored_flops(cfg) * decode_scored
                        + selected_flops(cfg) * decode_selected)
            + (layers - 1) * (scored_flops(cfg) * chunk_scored
                              + selected_flops(cfg) * chunk_selected))


def indexer_work(cfg, pairs, keys, queries, itemsize=2):
    """(flops, bytes) of the indexer over `pairs` scored (query,
    position) pairs: each pair's FLOPs; `keys` positions whose key
    (`indexer_head_dim` values) is read once (a decode row reads its
    own, a chunk's queries share theirs), and `queries` query rows'
    indexer queries and float32 weights.  All summed over the layers
    that score."""
    d = _dims(cfg)
    q = d["index_heads"] * (d["index_dim"] * itemsize + 4)
    return (scored_flops(cfg) * pairs,
            float(keys) * d["index_dim"] * itemsize + float(queries) * q)


def expert_work(cfg, assignments, touched, itemsize=2):
    """(flops, bytes) of the routed experts' grouped products: an
    ASSIGNMENT (one token sent to one expert) costs 2 FLOPs a weight of
    one expert and reads and writes its rows; the weights of the experts
    `touched` (summed over layers) are read once each."""
    d = _dims(cfg)
    rows = 3 * (d["hidden_size"] + d["moe_intermediate_size"])
    return (2.0 * expert_params(cfg) * assignments,
            (float(touched) * expert_params(cfg)
             + float(assignments) * rows) * itemsize)


def sparse_prefill_work(cfg, pairs, positions, queries, itemsize=2):
    """(flops, bytes) of a chunk's attention over the picks: `pairs`
    selected (query, position) pairs, K and V of `positions` (what the
    chunk's queries see, summed over chunks) read once, q read and o
    written for `queries`; all summed over the layers that attend."""
    d = _dims(cfg)
    kv = 2 * d["num_key_value_heads"] * d["head_dim"] * itemsize
    qo = 2 * d["num_attention_heads"] * d["head_dim"] * itemsize
    return (selected_flops(cfg) * pairs,
            float(positions) * kv + float(queries) * qo)


def pool_bytes_per_token(cfg, cache_width, itemsize=2):
    """Bytes one cached position takes over the layers: K and V, and
    the indexer's key as the pool lays it (`cache_width` lanes)."""
    d = _dims(cfg)
    return d["num_hidden_layers"] * itemsize * (
        2 * d["num_key_value_heads"] * d["head_dim"] + cache_width)


SUMMED = ("decode_rows", "indexer_positions", "selected_positions",
          "moe_assignments", "prefill_moe_assignments")
CHUNK_SUMMED = ("tokens", "scored_pairs", "selected_pairs",
                "attended_pairs")


def span_sums(steps):
    """{count: sum} over `steps` [(serving.step root, children)] (records
    of `benchmark.program_spans`): the roots' `SUMMED` counts, the
    ``serving.prefill`` children's `CHUNK_SUMMED` ones, and ``seen``: the
    positions a chunk's last query sees, summed over chunks (what a
    chunk's attention reads K and V of at least once).  A count no span
    carries is absent."""
    from benchmark import program_spans as ps
    out = {}

    def add(key, value):
        out[key] = out.get(key, 0) + value

    for root, kids in steps:
        for key in SUMMED:
            if key in root[ps.COUNTS]:
                add(key, root[ps.COUNTS][key])
        for kid in kids:
            counts = kid[ps.COUNTS]
            if kid[ps.NAME] != "serving.prefill" or "tokens" not in counts:
                continue
            add("chunks", 1)
            add("seen", counts["ctx"] + counts["tokens"])
            for key in CHUNK_SUMMED:
                if key in counts:
                    add(key, counts[key])
    return out
