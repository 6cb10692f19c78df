"""Plain reference of Laguna-S-2.1's decoder
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json,
``model_type`` ``laguna``; the catalog row of the model-configs guide):
pre-norm residual blocks under RMSNorm (eps 1e-6, no biases), attention
layers of two kinds in periods of one full and three window layers, a
dense SwiGLU in layer 0 and sigmoid-routed SwiGLU experts with one shared
expert in every other layer, a final RMSNorm and an untied head.

Per layer, for ``x`` [T, H], ``u = RMSNorm(x)``: ``h = x + Attn(u)``,
``y = h + FFN(RMSNorm(h))``.

* ``Attn``: ``H_l`` query heads (``num_attention_heads_per_layer``: 48 in
  a full layer, 72 in a window layer) over 8 kv heads of 128; RoPE turns
  the first ``r`` dims of every q and k head, pairs ``(i, i + r/2)``:
  window layers ``r = 128`` at ``10000 ** (-2i/128)``; full layers
  ``r = 64`` at YaRN's blended frequencies (theta 500,000, factor 128
  over 8,192, beta 32 / 1), cos and sin times ``attention_factor``.
  Scores ``q_h . k_(h // (H_l / 8)) / sqrt(128)``, softmax in float32
  over the keys ``j <= i`` (full) or ``i - 512 < j <= i`` (window).
  Every head's output is scaled by one scalar ``sigmoid(u W_g)_h``
  before ``W_o``.
* ``FFN_0(z) = (SiLU(z W_g) * z W_u) W_d``, width 12,288.
* ``FFN_l``, l >= 1: ``s = sigmoid(z W_r)`` over ALL ``router_experts``
  (256); the ``top_k`` (10) largest are picked;
  ``w = 2.5 s_picked / sum(s_picked)``.  THE SHARE: this chip holds
  experts ``[0, num_experts)`` of the published count (64 of 256);
  ``FFN_l(z) = sum over the HELD picked experts of w_e SwiGLU_e(z) +
  SwiGLU_shared(z)``.  What the absent experts would add is left out,
  here and in the program alike, and that partial result goes on to the
  next layer.  Every held expert is evaluated on every position under a
  mask of zero weights: no sort, no grouped product, nothing dropped.
* the vocabulary is the chip's slice: ids, logits and picks are over it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision;
it imports nothing of the program and takes nothing the program made.

Departures from the published description (each is also under ``assumed``
in the configuration file, which the config does not settle):
* the router is DeepSeek-V3's without its selection bias (sigmoid scores,
  weights normalised over the picked, times ``moe_routed_scaling_factor``):
  the config gives a scaling factor with ``norm_topk_prob`` and no scoring
  function, no key for a bias, ``moe_router_logit_softcapping`` 0 = none;
* ``gating: per-head`` is the headwise gate of arXiv:2505.06708: one
  sigmoid scalar a head from the layer's normed input, on the head's
  output before ``W_o``; no q/k norm and an ungated shared expert (no
  key for either);
* RoPE pairs ``(i, i + r/2)`` (the published family's ``rotate_half``);
  with seeded weights the interleaved pairing is a permutation of the
  columns of ``W_q`` and ``W_k``;
* every matrix Normal(0, 0.02), gains 1;
* for memory or time only: weights are stored in bfloat16 (the dtype the
  configuration serves in) and cast up where they are used, made leaf by
  leaf; attention runs one block of query rows at a time, the experts
  one at a time, the head one block of positions at a time; no block
  changes a number.

And one in what is compared, PR 28's rule (`references/kimi_vl.py`),
narrowed to the share: **a position whose routing is within rounding
distance of a tie THAT A HELD EXPERT IS PART OF carries no verdict.**
Where a picked expert leads one left out by under ``ROUTE_TIE`` and
either of the two is held here, activations held in bfloat16 cannot
decide whether the held one's output enters; ``next_token_gaps`` reports
a gap of 0 there.  A tie between two ABSENT experts moves only the sum
the weights are normalised by, by under ``ROUTE_TIE`` of ~7, and is held
to the float32 pass like any other position.

``precision`` is ``<numbers>[:<part>][+<fault>]``.  ``"float32"`` is the
reference; ``"fp8"`` the control (every tensor the bfloat16 program
rounds cut to fp8's e4m3 significand); ``"fp8:router"``,
``"fp8:experts"`` cut one part alone (``"bfloat16"`` in fp8's place cuts
to its significand).  A ``+fault`` is a WRONG model for a comparison to
catch: ``+no_window`` (the window layers see the whole context: a
program that forgot the band, or whose pool handed a block back too
early, reads like this or worse), ``+window_off_by_one``
(``i - 512 <= j``), ``+rope_swapped`` (each kind turns at the other's
frequencies, width and factor), ``+rotary_full`` (a full layer turns all
128 dims), ``+attention_factor_1``, ``+no_gate``, ``+scale_1``.
"""
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024        # head positions computed at a time
ATTN_ROWS = 256         # query rows against every key at a time
FULL, WINDOW = "full_attention", "sliding_attention"
ATTN = {
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "wg": "model.layers.{i}.self_attn.g_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
}
DENSE = {
    "d_gate": "model.layers.{i}.mlp.gate_proj.weight",
    "d_up": "model.layers.{i}.mlp.up_proj.weight",
    "d_down": "model.layers.{i}.mlp.down_proj.weight",
}
ROUTED = {
    "router": "model.layers.{i}.mlp.gate_weight",
    "e_gate": "model.layers.{i}.mlp.w_gate",
    "e_up": "model.layers.{i}.mlp.w_up",
    "e_down": "model.layers.{i}.mlp.w_down",
    "s_gate": "model.layers.{i}.mlp.shared_gate",
    "s_up": "model.layers.{i}.mlp.shared_up",
    "s_down": "model.layers.{i}.mlp.shared_down",
}
TOP = {"embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
       "head": "lm_head.weight"}
# a pick whose margin over an expert left out is under this is one that
# bfloat16 activations cannot decide: two bfloat16 steps (2 x 2**-8) of a
# score between 0.5 and 1, where the picked scores lie
ROUTE_TIE = 2.0 ** -7
SIGNIFICAND = {"fp8": 3, "bfloat16": 7}     # explicit bits of a control
PARTS = ("router", "experts")
FAULTS = ("no_window", "window_off_by_one", "rope_swapped", "rotary_full",
          "attention_factor_1", "no_gate", "scale_1")


@jax.tree_util.register_pytree_node_class
class Weights(dict):
    """The weights as a pytree whose static part carries what no shape
    tells: (top_k, routed scaling, norm_topk_prob, rms_norm_eps, kv heads,
    head size, window, the layers' kinds, the RoPE (inv_freq, r, factor)
    of the full kind, the window kind, and the full kind over a whole
    head, which only a fault reads)."""

    def __init__(self, leaves, hyper):
        super().__init__(leaves)
        self.hyper = tuple(hyper)

    def tree_flatten(self):
        return (dict(self),), self.hyper

    @classmethod
    def tree_unflatten(cls, hyper, children):
        return cls(children[0], hyper)


def rope_frequencies(params, head_dim):
    """(inv_freq tuple [r / 2], r, factor on cos and sin) of one kind's
    ``rope_parameters``: ``theta ** (-2i / r)``, or for ``yarn`` the
    published initialisation over the rotary width r:
    ``w_i = (1 - m_i) theta^(-2i/r) / factor + m_i theta^(-2i/r)``,
    ``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)``, ``lo, hi`` the floor /
    ceil of ``r ln(orig / (beta 2 pi)) / (2 ln theta)`` for beta_fast and
    beta_slow, clipped to ``[0, r - 1]``."""
    r = int(head_dim * float(params.get("partial_rotary_factor", 1.0)))
    theta = float(params["rope_theta"])
    inv = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if params.get("rope_type", "default") == "default":
        return tuple(inv), r, 1.0
    orig = float(params["original_max_position_embeddings"])
    dim = lambda beta: r * math.log(orig / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = max(math.floor(dim(float(params["beta_fast"]))), 0)
    hi = min(math.ceil(dim(float(params["beta_slow"]))), r - 1)
    out = []
    for i, w in enumerate(inv):
        m = 1.0 - min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append((1.0 - m) * w / float(params["factor"]) + m * w)
    return tuple(out), r, float(params.get("attention_factor", 1.0))


def n_layers(cfg):
    return int(cfg["num_hidden_layers"])


def hyper_of(cfg):
    d = int(cfg["head_dim"])
    ropes = tuple(rope_frequencies(cfg["rope_parameters"][kind], d)
                  for kind in (FULL, WINDOW))
    # for the fault `+rotary_full`: a full layer that turns the whole head
    ropes += (rope_frequencies(dict(cfg["rope_parameters"][FULL],
                                    partial_rotary_factor=1.0), d),)
    return (int(cfg["num_experts_per_tok"]),
            float(cfg["moe_routed_scaling_factor"]),
            bool(cfg.get("norm_topk_prob", True)),
            float(cfg["rms_norm_eps"]), int(cfg["num_key_value_heads"]),
            d, int(cfg["sliding_window"]),
            tuple(cfg["layer_types"][:n_layers(cfg)]), ropes)


def routed_width(cfg):
    """The router's outputs: the published count of experts, of which
    ``num_experts`` are held here."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def layer_shapes(cfg, i):
    """{leaf: shape} of layer `i`: attention by its own head count, then
    the dense SwiGLU (``mlp_only_layers``) or the router over every
    expert, the HELD experts and the shared expert."""
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    heads = int(cfg["num_attention_heads_per_layer"][i])
    kv = int(cfg["num_key_value_heads"])
    out = {"ln1": (h,), "wq": (h, heads * d), "wk": (h, kv * d),
           "wv": (h, kv * d), "wg": (h, heads), "wo": (heads * d, h),
           "ln2": (h,)}
    if i in [int(n) for n in cfg["mlp_only_layers"]]:
        f = int(cfg["intermediate_size"])
        out.update(d_gate=(h, f), d_up=(h, f), d_down=(f, h))
    else:
        held, f = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
        sf = int(cfg["shared_expert_intermediate_size"])
        out.update(router=(h, routed_width(cfg)), e_gate=(held, h, f),
                   e_up=(held, h, f), e_down=(held, f, h), s_gate=(h, sf),
                   s_up=(h, sf), s_down=(sf, h))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_weights(cfg, positions, seed, dtype=jnp.bfloat16):
    """Every weight from the seed, born on the device in the dtype it is
    served in, ONE LEAF A PROGRAM: Normal(0, initializer_range) matrices
    (embedding and head too), unit RMSNorm gains.  RoPE has no table, so
    `positions` changes no weight."""
    del positions
    dtype = jnp.dtype(dtype)
    std = float(cfg["initializer_range"])
    root = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    count = iter(range(1 << 30))

    def leaf(name, shape):
        if name in ("ln1", "ln2", "norm_f"):
            return jnp.ones(shape, dtype)
        return _normal(jax.random.fold_in(root, next(count)), shape, std,
                       dtype)

    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layers = [{k: leaf(k, s) for k, s in layer_shapes(cfg, i).items()}
              for i in range(n_layers(cfg))]
    return Weights({"embed": leaf("embed", (v, h)), "layers": layers,
                    "norm_f": leaf("norm_f", (h,)),
                    "head": leaf("head", (h, v))}, hyper_of(cfg))


def to_program(weights, cfg):
    """{program parameter name: array}: the same arrays, renamed."""
    out = {name: weights[k] for k, name in TOP.items()}
    for i, lp in enumerate(weights["layers"]):
        for k, a in lp.items():
            pat = ATTN.get(k) or DENSE.get(k) or ROUTED[k]
            out[pat.format(i=i)] = a
    return out


# ----------------------------------------------------------------- forward
def _round_significand(x, bits):
    m, e = jnp.frexp(x)
    scale = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _q(x, precision):
    """The rounding the control applies wherever the bfloat16 program
    rounds; the identity for the reference."""
    if precision == "float32":
        return x
    if precision in SIGNIFICAND:
        return _round_significand(x, SIGNIFICAND[precision])
    raise ValueError(f"unknown reference precision {precision!r}")


def _split(precision):
    """(numbers, part, fault) of ``<numbers>[:<part>][+<fault>]``."""
    rest, _, fault = precision.partition("+")
    lower, _, only = rest.partition(":")
    if only not in ("",) + PARTS or fault not in ("",) + FAULTS:
        raise ValueError(f"unknown reference precision {precision!r}")
    return lower, only, fault


def _of(precision, part):
    """The numbers `part` (one of PARTS, or "rest") is computed in."""
    lower, only, _ = _split(precision)
    return lower if only in ("", part) else "float32"


def _f32(t):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)


def _linear(x, w, precision):
    return _q(jnp.matmul(_q(x, precision), _q(w, precision),
                         precision="highest"), precision)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _swiglu(m, gate, up, down, precision):
    q_ = lambda t: _q(t, precision)
    return _linear(q_(jax.nn.silu(_linear(m, gate, precision))
                      * _linear(m, up, precision)), down, precision)


def _rope(x, rope):
    """Turn the first r dims of every head of x [T, heads, d] at
    positions 0 .. T - 1, pairs (i, i + r/2)."""
    inv, r, factor = rope
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def _attention(a, lp, kind, hyper, precision):
    kv, d, window, ropes = hyper[4], hyper[5], hyper[6], hyper[8]
    fault = _split(precision)[2]
    precision = _of(precision, "rest")
    q_ = lambda t: _q(t, precision)
    t = a.shape[0]
    heads = lp["wq"].shape[1] // d
    rope = ropes[(kind == WINDOW) != (fault == "rope_swapped")]
    if fault == "rotary_full" and kind == FULL:
        rope = ropes[2]
    if fault == "attention_factor_1":
        rope = rope[:2] + (1.0,)
    q = q_(_rope(_linear(a, lp["wq"], precision).reshape(t, heads, d), rope))
    k = q_(_rope(_linear(a, lp["wk"], precision).reshape(t, kv, d), rope))
    v = _linear(a, lp["wv"], precision).reshape(t, kv, d)
    group = heads // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    band = window if kind == WINDOW and fault != "no_window" else None
    if band is not None and fault == "window_off_by_one":
        band += 1

    def rows(start):
        """One block of query rows against every key; q head h reads kv
        head h // group."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        qb = qb.reshape(block, kv, group, d)
        s = jnp.einsum("qngd,knd->ngqk", qb, k, precision="highest") * scale
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if band is not None:
            seen &= j > i - band
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", q_(jax.nn.softmax(s, -1)), v,
                       precision="highest")
        return o.reshape(block, heads, d)

    block = min(ATTN_ROWS, t)
    if t % block:
        raise ValueError(f"sequence of {t} is no multiple of {block}")
    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads, d)
    if fault != "no_gate":      # one scalar a head
        o = o * jax.nn.sigmoid(_linear(a, lp["wg"], precision))[..., None]
    return _linear(q_(o).reshape(t, heads * d), lp["wo"], precision)


def _experts(m, lp, hyper, precision):
    """(the layer's output [T, H] from the held experts and the shared
    one, the margin [T] by which a pick leads an expert left out, over
    the pairs a HELD expert is part of)."""
    top_k, route_scale, norm_topk = hyper[:3]
    if _split(precision)[2] == "scale_1":
        route_scale = 1.0
    gate_in = _of(precision, "router")
    q_ = lambda t: _q(t, _of(precision, "rest"))
    precision = _of(precision, "experts")
    s = _q(jax.nn.sigmoid(_linear(m, lp["router"], gate_in)), gate_in)
    n = s.shape[-1]                                              # [T, E]
    held = lp["e_gate"].shape[0]        # the share: experts [0, held)
    lead, picked = jax.lax.top_k(s, min(top_k + 1, n))
    mask = jax.nn.one_hot(picked[:, :top_k], n, dtype=s.dtype).sum(1)
    here = jnp.arange(n) < held
    if n > top_k:
        # the weakest HELD pick over the best expert left out, and the
        # weakest pick over the best HELD expert left out
        weakest_held = jnp.min(jnp.where(here & (mask > 0), s, jnp.inf), -1)
        best_held_out = jnp.max(jnp.where(here & (mask == 0), s, -jnp.inf),
                                -1)
        margin = jnp.minimum(weakest_held - lead[:, top_k],
                             lead[:, top_k - 1] - best_held_out)
    else:
        margin = jnp.full(s.shape[:1], jnp.inf)
    w = s * mask
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * route_scale

    def one(y, ew):
        gate, up, down, we = _f32(ew)
        return y + we[:, None] * _swiglu(m, gate, up, down, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (lp["e_gate"], lp["e_up"], lp["e_down"],
                         w[:, :held].T))
    return q_(y) + _swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"],
                           precision), margin


def hidden_fn(weights, ids, precision="float32"):
    """[T] token ids -> ([T, H] hidden states after the final norm, [T]
    the narrowest margin of a position's picks over the layers)."""
    hyper = weights.hyper
    eps, kinds = hyper[3], hyper[7]
    rest = _of(precision, "rest")
    q_ = lambda t: _q(t, rest)
    margin = jnp.full(ids.shape, jnp.inf)
    x = q_(weights["embed"][ids].astype(jnp.float32))
    for lp, kind in zip(weights["layers"], kinds):
        small = _f32({k: a for k, a in lp.items() if a.ndim < 3})
        a = q_(_rms_norm(x, small["ln1"], eps))
        x = q_(x + _attention(a, small, kind, hyper, precision))
        m = q_(_rms_norm(x, small["ln2"], eps))
        if "d_gate" in lp:
            y = _swiglu(m, small["d_gate"], small["d_up"], small["d_down"],
                        rest)
        else:
            # the experts' stacks stay in their storage dtype until the
            # loop reaches each expert (a cast up changes no number)
            big = {k: a for k, a in lp.items() if a.ndim == 3}
            y, led = _experts(m, {**small, **big}, hyper, precision)
            margin = jnp.minimum(margin, led)
        x = q_(x + y)
    return q_(_rms_norm(x, weights["norm_f"].astype(jnp.float32),
                        eps)), margin


def logits_fn(weights, ids, heads=None, precision="float32"):
    """[B, S] token ids -> [B, S, V] float32 logits (small sizes: the
    whole matrix at once).  `heads` is not read: the layers' head counts
    are their weights' shapes."""
    head = weights["head"].astype(jnp.float32)
    return jnp.stack([_linear(hidden_fn(weights, row, precision)[0], head,
                              _of(precision, "rest")) for row in ids])


@jax.jit
def undecided(weights, tokens):
    """[1, T] tokens -> [T] bool: the positions one of whose picks is
    within ROUTE_TIE of a tie that a held expert is part of, in the
    float32 pass; they carry no verdict."""
    return hidden_fn(weights, tokens[0])[1] < ROUTE_TIE


def next_token_gaps(weights, tokens, chosen, heads=None,
                    precision="float32"):
    """For one sequence `tokens` [1, T] and the token `chosen` [T] that
    followed each position: (best logit, chosen token's logit, argmax)
    per position, from the full forward pass, the head one block of
    positions at a time.  At a position whose routing this pass leaves
    `undecided`, the chosen token's logit is reported as the best: no
    verdict, a gap of 0 (the module's departures).  The float32 pass
    says on standard error how many of the served positions (the run
    from the first to the last token chosen) were left so.  `heads` (the
    harness passes the configuration's ``num_attention_heads``) is not
    read: the layers' head counts are their weights' shapes."""
    del heads
    best, took, arg, tied = _gaps(weights, tokens, chosen, precision)
    if precision == "float32":
        served = np.flatnonzero(np.asarray(chosen))
        if len(served):
            lo, hi = served[0], served[-1] + 1
            left = int(np.asarray(tied)[lo:hi].sum())
            print(f"laguna reference: {hi - lo} served positions, {left} "
                  f"within ROUTE_TIE of a tie a held expert is part of and "
                  f"left uncompared ({100.0 * left / (hi - lo):.1f}%)",
                  file=sys.stderr, flush=True)
    return best, took, arg


@functools.partial(jax.jit, static_argnums=(3,))
def _gaps(weights, tokens, chosen, precision):
    x, margin = hidden_fn(weights, tokens[0], precision)
    precision = _of(precision, "rest")
    head = weights["head"].astype(jnp.float32)
    t = x.shape[0]
    block = min(ROW_BLOCK, t)

    def rows(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
        cb = jax.lax.dynamic_slice_in_dim(chosen, start, block, 0)
        logits = _linear(xb, head, precision)
        return (jnp.max(logits, -1),
                jnp.take_along_axis(logits, cb[:, None], -1)[:, 0],
                jnp.argmax(logits, -1))

    best, took, arg = jax.lax.map(rows, jnp.arange(0, t, block))
    best, took = best.reshape(t), took.reshape(t)
    tied = margin < ROUTE_TIE
    return best, jnp.where(tied, best, took), arg.reshape(t), tied
