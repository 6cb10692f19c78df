"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone -- never from what a trace happens to hold.  Keys are the
configuration file's (Hugging Face names): hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, head_dim, intermediate_size,
vocab_size, hidden_act.
"""


def _dims(cfg):
    h = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads") or heads)
    d = int(cfg.get("head_dim") or h // heads)
    return (h, int(cfg["num_hidden_layers"]), heads, kv, d,
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def matmul_params(cfg):
    """Weights that every token multiplies: attention projections and
    feed-forward of every layer, plus the output head.  Embedding rows
    are looked up, not multiplied, and do not count (a tied head counts
    once, as the head)."""
    h, layers, heads, kv, d, f, v = _dims(cfg)
    attn = h * heads * d + 2 * h * kv * d + heads * d * h
    gated = str(cfg.get("hidden_act", "gelu")).startswith(("silu", "swi"))
    mlp = (3 if gated else 2) * h * f
    return layers * (attn + mlp) + v * h


def attention_flops_causal(cfg, seq):
    """Forward FLOPs of causal attention for ONE sequence in ONE layer:
    QK^T and PV over the lower triangle, 2 * seq^2 * heads * d."""
    _, _, heads, _, d, _, _ = _dims(cfg)
    return 2.0 * seq * seq * heads * d


def train_flops_per_token(cfg, seq):
    """Model FLOPs of forward + backward per trained token: 6 per matmul
    weight, plus causal attention (backward twice the forward).
    Recomputation is not counted."""
    layers = _dims(cfg)[1]
    return 6.0 * matmul_params(cfg) \
        + 3.0 * layers * attention_flops_causal(cfg, seq) / seq


def mfu(flops_per_s, chips, peak_flops):
    """Share (0..1) of the chips' peak."""
    return flops_per_s / (chips * peak_flops)


def flash_step_work(cfg, batch, seq, itemsize=2):
    """(flops, bytes) that causal attention forward + backward of one
    training step needs over all layers.  Bytes: the forward reads q, k,
    v and writes o; the backward reads q, k, v, o, do and writes dq, dk,
    dv -- each [batch, seq, heads or kv heads, d] once."""
    _, layers, heads, kv, d, _, _ = _dims(cfg)
    flops = 3.0 * layers * batch * attention_flops_causal(cfg, seq)
    q_like = batch * seq * heads * d * itemsize
    kv_like = batch * seq * kv * d * itemsize
    # fwd: q,o (q-like) k,v (kv-like); bwd: q,o,do,dq (q-like) k,v,dk,dv
    return flops, layers * (6.0 * q_like + 6.0 * kv_like)


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds, which bound binds) on one chip."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def serve_flops(cfg, tokens_processed):
    """2 FLOPs per matmul weight per token the engine pushed through the
    model (prompt tokens prefilled + tokens decoded)."""
    return 2.0 * matmul_params(cfg) * tokens_processed


def paged_decode_bytes(cfg, context_sum, itemsize=2):
    """Bytes of K and V that decoding needs: for every emitted token its
    context length x kv heads x d x 2 (K and V) x itemsize x layers.
    `context_sum` is the sum of those context lengths."""
    _, layers, _, kv, d, _, _ = _dims(cfg)
    return float(context_sum) * kv * d * 2 * itemsize * layers
