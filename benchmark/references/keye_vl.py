"""Plain reference of Keye-VL-2.0-30B-A3B's language model
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type`` ``KeyeVL2``; the catalog row of the model-configs guide):
pre-norm residual blocks under RMSNorm (eps 1e-6, no biases), GQA whose
queries attend only what a learned indexer picks (DeepSeek-V3.2's sparse
attention), softmax-routed SwiGLU experts in every layer, a final RMSNorm
and an untied head.

Per layer, for ``x`` [T, H], ``u = RMSNorm(x)``: ``h = x + Attn(u)``,
``y = h + MoE(RMSNorm(h))``.

* ``Attn``: ``q = u W_q`` [32, 128], ``k = u W_k``, ``v = u W_v`` [4, 128];
  an RMSNorm over each head's 128 dims on q and k (gain, eps 1e-6), then
  RoPE at ``1e7 ** (-2i/128)`` over the whole head, pairs ``(i, i + 64)``.
* the indexer: ``q^I = u W_qI`` [16, 64], ``k^I = LayerNorm(u W_kI)``
  [64] (gain, bias, eps 1e-6), RoPE at ``1e7 ** (-2i/32)`` on the first
  32 dims of both, pairs ``(i, i + 16)``; ``w = u W_w / sqrt(16 * 64)``;
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])`` for ``s <= t``,
  float32.  ``S_t`` = the 2,048 positions ``s <= t`` of the largest
  ``I[t, s]`` (``lax.top_k``; every ``s <= t`` while there are fewer).
* ``o[t, h] = sum_(s in S_t) softmax_s(q[t, h] . k[s, h // 8] / sqrt(128))
  v[s, h // 8]``, softmax in float32; ``Attn = [o_1 .. o_32] W_o``.
* ``MoE(z)``: ``p = softmax(z W_r)`` over 128 experts, float32; the 8
  largest picked, ``w_e = p_e / sum_picked p``; ``sum_e w_e
  (SiLU(z W_g,e) * z W_u,e) W_d,e``, width 768, no shared expert.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision;
it imports nothing of the program and takes nothing the program made.
For memory and time only (no block changes a number): weights are stored
in bfloat16 (the dtype the configuration serves in) and cast up where
they are used; attention takes `ATTN_ROWS` queries at a time against key
blocks of `KEY_BLOCK`, online softmax over the keys a query may attend
(a mask of the selection), and skips the blocks past the queries; the
experts go one block of positions at a time through a product grouped
by expert; and nothing past the last token of the checked request is
computed (the harness pads every request to the configuration's
positions; causality makes the padding irrelevant to what is compared).

And one rule in what is compared, `kimi_vl.py`'s, re-derived for a
softmax over 128 experts: **a position whose routing is within rounding
distance of a tie carries no verdict.**  The probabilities are ~1/128, so the margin is
taken on the router's LOGITS (softmax is monotone): where the 8th pick's
logit leads the 9th by under ``ROUTE_TIES`` bfloat16 steps of the larger,
activations held in bfloat16 cannot decide which expert enters;
``next_token_gaps`` reports a gap of 0 there.  There is NO such rule for
the indexer's selection: at tens of thousands of positions the 2,048th
and 2,049th scores always lie close, and such a rule would decide
nothing; the limit holds the swaps at the boundary.

``precision`` is ``<numbers>[+<fault>]``.  ``"float32"`` is the
reference; ``"fp8"`` the control (every tensor the bfloat16 program
rounds cut to fp8's e4m3 significand; ``"bfloat16"`` cuts to bfloat16's).
A ``+fault`` is a WRONG model that the check has to catch: ``+dense``
(the selection left out: every ``s <= t`` attended) and ``+recent`` (the
last 2,048 positions in place of the indexer's picks).
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024        # head positions computed at a time
ATTN_ROWS = 128         # queries against the keys at a time
KEY_BLOCK = 2048        # keys an attention step reduces
EXPERT_ROWS = 2048      # positions the experts take at a time
ATTN = {
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "q_norm": "model.layers.{i}.self_attn.q_norm.weight",
    "k_norm": "model.layers.{i}.self_attn.k_norm.weight",
    "i_wq": "model.layers.{i}.self_attn.indexer.wq.weight",
    "i_wk": "model.layers.{i}.self_attn.indexer.wk.weight",
    "i_ww": "model.layers.{i}.self_attn.indexer.weights_proj.weight",
    "i_kn_w": "model.layers.{i}.self_attn.indexer.k_norm_weight",
    "i_kn_b": "model.layers.{i}.self_attn.indexer.k_norm_bias",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
    "router": "model.layers.{i}.mlp.gate_weight",
    "e_gate": "model.layers.{i}.mlp.w_gate",
    "e_up": "model.layers.{i}.mlp.w_up",
    "e_down": "model.layers.{i}.mlp.w_down",
}
TOP = {"embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
       "head": "lm_head.weight"}
ONES = ("ln1", "ln2", "norm_f", "q_norm", "k_norm", "i_kn_w")
# a pick whose logit leads the first left out by under this many bfloat16
# steps of the logit is one that bfloat16 activations cannot decide
ROUTE_TIES = 2.0
SIGNIFICAND = {"fp8": 3, "bfloat16": 7}     # explicit bits of a control
FAULTS = ("dense", "recent")


@jax.tree_util.register_pytree_node_class
class Weights(dict):
    """The weights as a pytree whose static part carries what no shape
    tells: (top_k, norm_topk_prob, rms_norm_eps, kv heads, head size,
    rope theta, indexer heads, indexer head size, indexer rotary dims,
    indexer top-k)."""

    def __init__(self, leaves, hyper):
        super().__init__(leaves)
        self.hyper = tuple(hyper)

    def tree_flatten(self):
        return (dict(self),), self.hyper

    @classmethod
    def tree_unflatten(cls, hyper, children):
        return cls(children[0], hyper)


def n_layers(cfg):
    return int(cfg["num_hidden_layers"])


def hyper_of(cfg):
    sa = cfg["sa_config"]
    return (int(cfg["num_experts_per_tok"]),
            bool(cfg.get("norm_topk_prob", True)),
            float(cfg["rms_norm_eps"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), float(cfg["rope_theta"]),
            int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(cfg["model_kwargs"]["index_rope_dim"]), int(sa["topk"]))


def layer_shapes(cfg):
    """{leaf: shape} of one layer."""
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    sa = cfg["sa_config"]
    n, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    e, f = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    return {"ln1": (h,), "wq": (h, heads * d), "wk": (h, kv * d),
            "wv": (h, kv * d), "wo": (heads * d, h), "q_norm": (d,),
            "k_norm": (d,), "i_wq": (h, n * di), "i_wk": (h, di),
            "i_ww": (h, n), "i_kn_w": (di,), "i_kn_b": (di,), "ln2": (h,),
            "router": (h, e), "e_gate": (e, h, f), "e_up": (e, h, f),
            "e_down": (e, f, h)}


def parameter_count(cfg):
    """Parameters of the configuration as this chip holds it."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layer = sum(int(np.prod(s)) for s in layer_shapes(cfg).values())
    return n_layers(cfg) * layer + 2 * v * h + h


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_weights(cfg, positions, seed, dtype=jnp.bfloat16):
    """Every weight from the seed, born on the device in the dtype it is
    served in, ONE LEAF A PROGRAM: Normal(0, initializer_range) matrices
    (embedding and head too), unit gains, a zero LayerNorm bias.  RoPE
    has no table, so `positions` changes no weight."""
    del positions
    dtype = jnp.dtype(dtype)
    std = float(cfg["initializer_range"])
    root = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    count = iter(range(1 << 30))

    def leaf(name, shape):
        if name in ONES:
            return jnp.ones(shape, dtype)
        if name == "i_kn_b":
            return jnp.zeros(shape, dtype)
        return _normal(jax.random.fold_in(root, next(count)), shape, std,
                       dtype)

    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layers = [{k: leaf(k, s) for k, s in layer_shapes(cfg).items()}
              for _ in range(n_layers(cfg))]
    return Weights({"embed": leaf("embed", (v, h)), "layers": layers,
                    "norm_f": leaf("norm_f", (h,)),
                    "head": leaf("head", (h, v))}, hyper_of(cfg))


def to_program(weights, cfg):
    """{program parameter name: array}: the same arrays, renamed."""
    out = {name: weights[k] for k, name in TOP.items()}
    for i, lp in enumerate(weights["layers"]):
        for k, a in lp.items():
            out[ATTN[k].format(i=i)] = a
    return out


# ----------------------------------------------------------------- forward
def _round_significand(x, bits):
    m, e = jnp.frexp(x)
    scale = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _split(precision):
    """(numbers, fault) of ``<numbers>[+<fault>]``."""
    numbers, _, fault = precision.partition("+")
    if (numbers != "float32" and numbers not in SIGNIFICAND) \
            or fault not in ("",) + FAULTS:
        raise ValueError(f"unknown reference precision {precision!r}")
    return numbers, fault


def _q(x, numbers):
    """The rounding the control applies wherever the bfloat16 program
    rounds; the identity for the reference."""
    return x if numbers == "float32" \
        else _round_significand(x, SIGNIFICAND[numbers])


def _f32(t):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)


def _linear(x, w, numbers):
    return _q(jnp.matmul(_q(x, numbers), _q(w.astype(jnp.float32), numbers),
                         precision="highest"), numbers)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta, r):
    """Turn the first r dims of every head of x [T, heads, d] at
    positions 0 .. T - 1, pairs (i, i + r/2)."""
    inv = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def _blocks(fn, total, block, live, out_shape):
    """`fn(start)` over blocks of `total` rows; a block that starts at
    or past `live` is zeros (nothing past the request is read)."""
    def one(start):
        return jax.lax.cond(start < live, fn,
                            lambda s: jnp.zeros(out_shape, jnp.float32),
                            start)
    out = jax.lax.map(one, jnp.arange(0, total, block))
    return out.reshape((total,) + out_shape[1:])


def _attention(a, lp, hyper, precision, live):
    _, _, eps, kv, d, theta, n, di, r, topk = hyper
    numbers, fault = _split(precision)
    q_ = lambda t: _q(t, numbers)
    t = a.shape[0]
    heads = lp["wq"].shape[1] // d
    group = heads // kv
    q = _linear(a, lp["wq"], numbers).reshape(t, heads, d)
    k = _linear(a, lp["wk"], numbers).reshape(t, kv, d)
    v = _linear(a, lp["wv"], numbers).reshape(t, kv, d)
    q = q_(_rope(q_(_rms_norm(q, lp["q_norm"], eps)), theta, d))
    k = q_(_rope(q_(_rms_norm(k, lp["k_norm"], eps)), theta, d))
    # the indexer
    qi = q_(_rope(_linear(a, lp["i_wq"], numbers).reshape(t, n, di),
                  theta, r))
    ki = _linear(a, lp["i_wk"], numbers)
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(
        jnp.square(ki - mu).mean(-1, keepdims=True) + eps) \
        * lp["i_kn_w"] + lp["i_kn_b"]
    ki = q_(_rope(q_(ki)[:, None, :], theta, r)[:, 0])
    wi = jnp.matmul(_q(a, numbers), lp["i_ww"], precision="highest") \
        * (n * di) ** -0.5
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    block = min(ATTN_ROWS, t)
    kblock = min(KEY_BLOCK, t)
    if t % block or t % kblock:
        raise ValueError(f"sequence of {t} is no multiple of {kblock}")

    def rows(start):
        i = (start + jnp.arange(block))[:, None]            # [B, 1]
        j = jnp.arange(t)[None, :]                          # [1, T]
        seen = j <= i
        if fault == "dense":
            picked = seen
        elif fault == "recent":
            picked = seen & (j > i - topk)
        else:
            qb = jax.lax.dynamic_slice_in_dim(qi, start, block, 0)
            wb = jax.lax.dynamic_slice_in_dim(wi, start, block, 0)
            score = jnp.einsum(
                "bh,bhs->bs", wb, jnp.maximum(jnp.einsum(
                    "bhe,se->bhs", qb, ki, precision="highest"), 0.0),
                precision="highest")
            score = jnp.where(seen, score, -jnp.inf)
            top = jax.lax.top_k(score, min(topk, t))[1]
            picked = jnp.zeros((block, t), bool).at[
                jnp.arange(block)[:, None], top].set(True) & seen
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0).reshape(
            block, kv, group, d)

        def keys(carry, ks):
            m, l, acc = carry

            def step(_):
                kb = jax.lax.dynamic_slice_in_dim(k, ks, kblock, 0)
                vb = jax.lax.dynamic_slice_in_dim(v, ks, kblock, 0)
                pb = jax.lax.dynamic_slice_in_dim(picked, ks, kblock, 1)
                s = jnp.einsum("qngd,knd->qngk", qb, kb,
                               precision="highest") * scale
                s = jnp.where(pb[:, None, None, :], s, -jnp.inf)
                m_new = jnp.maximum(m, s.max(-1))
                m_at = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - m_at[..., None])
                corr = jnp.exp(m - m_at)
                return (m_new, l * corr + p.sum(-1),
                        acc * corr[..., None] + jnp.einsum(
                            "qngk,knd->qngd", p, vb, precision="highest"))

            # key blocks past the block's last query are never read
            return jax.lax.cond(ks <= start + block - 1, step,
                                lambda _: (m, l, acc), None), None

        shape = (block, kv, group)
        (m, l, acc), _ = jax.lax.scan(
            keys, (jnp.full(shape, -jnp.inf), jnp.zeros(shape),
                   jnp.zeros(shape + (d,))), jnp.arange(0, t, kblock))
        # the softmax's rounding where the program rounds p
        return q_(acc / l[..., None]).reshape(block, heads, d)

    o = _blocks(rows, t, block, live, (block, heads, d))
    return _linear(q_(o).reshape(t, heads * d), lp["wo"], numbers), ki


def _experts(m, lp, hyper, precision, live):
    """(the layer's output [T, H], the margin [T] of the 8th pick's
    logit over the 9th, in bfloat16 steps of the larger)."""
    top_k, norm_topk = hyper[:2]
    numbers = _split(precision)[0]
    logits = jnp.matmul(_q(m, numbers), lp["router"].astype(jnp.float32),
                        precision="highest")                    # [T, E]
    lead, picked = jax.lax.top_k(logits, top_k + 1)
    edge = jnp.maximum(jnp.abs(lead[:, top_k - 1]), jnp.abs(lead[:, top_k]))
    step = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(edge, 1e-30))) - 7)
    margin = (lead[:, top_k - 1] - lead[:, top_k]) / step
    p = jax.nn.softmax(logits, -1)
    w = jnp.take_along_axis(p, picked[:, :top_k], -1)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    picked = picked[:, :top_k]
    experts, t = lp["e_gate"].shape[0], m.shape[0]
    block = min(EXPERT_ROWS, t)

    def rows(start):
        mb = jax.lax.dynamic_slice_in_dim(m, start, block, 0)
        pb = jax.lax.dynamic_slice_in_dim(picked, start, block, 0).reshape(-1)
        wb = jax.lax.dynamic_slice_in_dim(w, start, block, 0)
        order = jnp.argsort(pb)
        x = _q(mb, numbers)[order // top_k]
        sizes = jnp.bincount(pb, length=experts).astype(jnp.int32)
        dot = lambda x_, name: jax.lax.ragged_dot(
            x_, _q(lp[name].astype(jnp.float32), numbers), sizes,
            precision=jax.lax.Precision.HIGHEST)
        h = _q(_q(jax.nn.silu(_q(dot(x, "e_gate"), numbers)), numbers)
               * _q(dot(x, "e_up"), numbers), numbers)
        y = _q(dot(h, "e_down"), numbers)[jnp.argsort(order)]
        return jnp.einsum("bkd,bk->bd", y.reshape(block, top_k, -1), wb,
                          precision="highest")

    y = _blocks(rows, t, block, live, (block, m.shape[1]))
    return _q(y, numbers), margin


def hidden_fn(weights, ids, precision="float32", live=None):
    """[T] token ids -> ([T, H] hidden states after the final norm, [T]
    the narrowest routing margin of a position over the layers, in
    bfloat16 steps).  Positions from `live` on are not computed."""
    return _layers(weights, ids, precision, live)[:2]


def index_keys(weights, ids):
    """[T] token ids -> [layers, T, indexer head size]: the indexer's key
    of every position in every layer (what the program caches)."""
    return jnp.stack(_layers(weights, ids, "float32", None)[2])


def _layers(weights, ids, precision, live):
    hyper = weights.hyper
    eps = hyper[2]
    numbers = _split(precision)[0]
    live = ids.shape[0] if live is None else live
    q_ = lambda t: _q(t, numbers)
    margin = jnp.full(ids.shape, jnp.inf)
    keys = []
    x = q_(weights["embed"][ids].astype(jnp.float32))
    for lp in weights["layers"]:
        small = _f32({k: a for k, a in lp.items() if a.ndim < 3})
        big = {k: a for k, a in lp.items() if a.ndim == 3}
        a = q_(_rms_norm(x, small["ln1"], eps))
        out, ki = _attention(a, small, hyper, precision, live)
        keys.append(ki)
        x = q_(x + out)
        mm = q_(_rms_norm(x, small["ln2"], eps))
        y, led = _experts(mm, {**small, **big}, hyper, precision, live)
        margin = jnp.minimum(margin, led)
        x = q_(x + y)
    return q_(_rms_norm(x, weights["norm_f"].astype(jnp.float32),
                        eps)), margin, keys


def logits_fn(weights, ids, heads=None, precision="float32"):
    """[B, S] token ids -> [B, S, V] float32 logits (small sizes: the
    whole matrix at once).  `heads` is not read."""
    head = weights["head"].astype(jnp.float32)
    numbers = _split(precision)[0]
    return jnp.stack([_linear(hidden_fn(weights, row, precision)[0], head,
                              numbers) for row in ids])


def undecided(weights, tokens):
    """[1, T] tokens -> [T] bool: the positions one of whose routings is
    within ROUTE_TIES bfloat16 steps of a tie in the float32 pass."""
    return _margin(weights, tokens) < ROUTE_TIES


@jax.jit
def _margin(weights, tokens):
    return hidden_fn(weights, tokens[0])[1]


def _live(tokens, chosen, block):
    """Positions to compute: past the last token fed or chosen (the
    request), rounded up to whole blocks, with a margin for a last
    token that happens to be id 0."""
    used = np.flatnonzero(np.asarray(tokens[0]) | np.asarray(chosen))
    end = (int(used[-1]) + 1 if len(used) else 0) + 64
    return min(-(-end // block) * block, int(tokens.shape[1]))


def next_token_gaps(weights, tokens, chosen, heads=None,
                    precision="float32"):
    """For one sequence `tokens` [1, T] and the token `chosen` [T] that
    followed each position: (best logit, chosen token's logit, argmax)
    per position, from the full forward pass, the head one block of
    positions at a time.  At a position whose routing this pass leaves
    `undecided`, the chosen token's logit is reported as the best: no
    verdict, a gap of 0 (the module's rule).  The float32 pass says on
    standard error how many of the served positions (the run from the
    first to the last token chosen) were left so.  `heads` is not read."""
    del heads
    live = _live(tokens, chosen, ROW_BLOCK)
    best, took, arg, tied = _gaps(weights, tokens, chosen, jnp.int32(live),
                                  precision)
    if precision == "float32":
        served = np.flatnonzero(np.asarray(chosen))
        if len(served):
            lo, hi = served[0], served[-1] + 1
            left = int(np.asarray(tied)[lo:hi].sum())
            print(f"keye_vl reference: {hi - lo} served positions, {left} "
                  f"within {ROUTE_TIES:g} bfloat16 steps of a routing tie "
                  f"and left uncompared ({100.0 * left / (hi - lo):.1f}%)",
                  file=sys.stderr, flush=True)
    return best, took, arg


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(weights, tokens, chosen, live, precision):
    x, margin = hidden_fn(weights, tokens[0], precision, live)
    numbers = _split(precision)[0]
    head = weights["head"].astype(jnp.float32)
    t = x.shape[0]
    block = min(ROW_BLOCK, t)

    def rows(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
        cb = jax.lax.dynamic_slice_in_dim(chosen, start, block, 0)
        logits = _linear(xb, head, numbers)
        return (jnp.max(logits, -1),
                jnp.take_along_axis(logits, cb[:, None], -1)[:, 0],
                jnp.argmax(logits, -1).astype(jnp.float32))

    best, took, arg = jax.lax.map(rows, jnp.arange(0, t, block))
    best, took = best.reshape(t), took.reshape(t)
    tied = margin < ROUTE_TIES
    return (best, jnp.where(tied, best, took),
            arg.reshape(t).astype(jnp.int32), tied)
