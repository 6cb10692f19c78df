"""Plain reference of Solar-Open2-250B's decoder
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type`` ``solar_open2``; the catalog row of the model-configs
guide): pre-norm residual blocks under RMSNorm, a period of one gated
NoPE GQA layer and three gated delta-rule layers (Kimi Delta Attention,
arXiv:2510.26692), every layer followed by sigmoid-routed SwiGLU experts
with one shared expert, a final RMSNorm and an untied head.  No
positional encoding anywhere (``use_rope`` false).

Per layer, for ``x`` [T, H] and ``n = RMSNorm(x)``:

* GQA layer (``layer_idx`` in ``gqa_layers``): ``q = n W_q`` (heads x d),
  ``k = n W_k``, ``v = n W_v`` (kv heads x d); causal softmax attention
  at scale ``d ** -0.5``, q head h reads kv head ``h // (heads / kv)``;
  output ``(sigmoid(n W_g) * attn) W_o``.
* KDA layer (all others): ``[q | k | v] = SiLU(conv(n W_qkv))`` with a
  causal depthwise convolution of ``short_conv_kernel_size`` taps over
  time (zeros before the first position); per head q and k divided by
  ``sqrt(sum of squares + 1e-6)``, q scaled by ``d ** -0.5``; per
  channel ``g_t = -exp(A_log_h) softplus(n W_f1 W_f2 + dt_bias)``,
  ``beta_t = 2 sigmoid(n w_beta)`` (``kda_allow_neg_eigval``), and the
  PLAIN RECURRENCE, one position after the other (`lax.scan`; no chunks,
  no kernel):

      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  output ``(RMSNorm_head(o_t) * sigmoid(n W_g1 W_g2)) W_o``.
* expert layer (every layer): ``s = sigmoid(m W_r)`` over ALL
  ``router_experts`` experts; the ``top_k`` largest of
  ``s + b`` are picked (``b`` only picks, never weighs);
  ``w = s_picked / sum(s_picked) * routed_scaling_factor``.  THE SHARE:
  this chip holds experts ``[0, n_routed_experts)`` of the published
  count; ``y = sum over the HELD picked experts of w_e SwiGLU_e(m) +
  SwiGLU_shared(m)``.  What the absent experts would add is left out,
  here and in the program alike, and that partial result goes on to the
  next layer.  Every held expert is evaluated on every position under a
  mask of zero weights: no sort, no grouped product, nothing dropped.
* the vocabulary is the chip's slice: ids, logits and picks are over it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision;
it imports nothing of the program and takes nothing the program made.

Departures from the published description (each is also under
``assumed`` in the configuration file, which the config does not settle):
* the router is DeepSeek-V3's (sigmoid scores, a selection bias drawn
  Normal(0, 0.1)): the config uses that family's key names and gives no
  scoring function.  The bias is drawn from the configuration's
  ``router_bias_seed``, not from the run's seed: it decides which
  experts are popular and so how many of the held ones a step touches
  (54-66 of 160 over eight seeds, my CPU count, PR 32), which is the
  amount of work;
* the GQA gate is elementwise (hidden -> heads x d) from the layer's
  normed input; the GQA layer has no q/k norm; ``W_f`` and ``W_g`` of a
  KDA layer are pairs of rank ``kda_gate_rank`` (128) as
  ``kda_use_full_proj`` false says, the rank itself assumed;
* ``A_log = log U(1, 16)`` per head and ``dt_bias`` the inverse
  softplus of U(1e-3, 0.1) per channel, from the seed (the published
  initialisation of the gated delta-rule family); every matrix
  Normal(0, 0.02);
* for memory or time only: weights are stored in bfloat16 (the dtype the
  configuration serves in) and cast up where they are used, made leaf by
  leaf; attention runs one block of query rows at a time, the experts
  one at a time, the head one block of positions at a time; no block
  changes a number.

And one in what is compared, PR 28's rule (`references/kimi_vl.py`),
copied: **a position whose routing is within rounding distance of a tie
carries no verdict.**  Where the last expert picked leads the first left
out by under ``ROUTE_TIE`` in any layer, activations held in bfloat16
cannot decide the pick; ``next_token_gaps`` reports a gap of 0 there and
``undecided`` says which positions those are.

``precision``: ``"float32"`` is the reference; ``"fp8"`` the control
(every tensor the bfloat16 program rounds cut to fp8's e4m3
significand); ``"fp8:router"``, ``"fp8:state"`` and ``"fp8:experts"``
cut one part alone (the router's product and scores; the recurrent state
after every position and the convolution's inputs; the routed and shared
experts' products).  ``"bfloat16"`` in fp8's place cuts to its
significand.
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024        # query rows / head positions computed at a time
COMMON = {
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
    "router": "model.layers.{i}.mlp.gate_weight",
    "bias": "model.layers.{i}.mlp.score_bias",
    "e_gate": "model.layers.{i}.mlp.w_gate",
    "e_up": "model.layers.{i}.mlp.w_up",
    "e_down": "model.layers.{i}.mlp.w_down",
    "s_gate": "model.layers.{i}.mlp.shared_gate",
    "s_up": "model.layers.{i}.mlp.shared_up",
    "s_down": "model.layers.{i}.mlp.shared_down",
}
GQA = {
    "wq": "model.layers.{i}.mixer.q_proj.weight",
    "wk": "model.layers.{i}.mixer.k_proj.weight",
    "wv": "model.layers.{i}.mixer.v_proj.weight",
    "wg": "model.layers.{i}.mixer.g_proj.weight",
    "wo": "model.layers.{i}.mixer.o_proj.weight",
}
KDA = {
    "wqkv": "model.layers.{i}.mixer.qkv_proj.weight",
    "conv": "model.layers.{i}.mixer.conv_weight",
    "wf1": "model.layers.{i}.mixer.f_a_proj.weight",
    "wf2": "model.layers.{i}.mixer.f_b_proj.weight",
    "wg1": "model.layers.{i}.mixer.g_a_proj.weight",
    "wg2": "model.layers.{i}.mixer.g_b_proj.weight",
    "wbeta": "model.layers.{i}.mixer.b_proj.weight",
    "a_log": "model.layers.{i}.mixer.A_log",
    "dt_bias": "model.layers.{i}.mixer.dt_bias",
    "o_norm": "model.layers.{i}.mixer.o_norm.weight",
    "kwo": "model.layers.{i}.mixer.o_proj.weight",
}
TOP = {"embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
       "head": "lm_head.weight"}
BIAS_STD = 0.1          # the seeded, NON-zero selection bias of a router
L2_EPS = 1e-6
# a pick whose margin over the first expert left out is under this is one
# that bfloat16 activations cannot decide: two bfloat16 steps (2 x 2**-8)
# of a score between 0.5 and 1, where the picked `s + b` lie
ROUTE_TIE = 2.0 ** -7
SIGNIFICAND = {"fp8": 3, "bfloat16": 7}     # explicit bits of a control
PARTS = ("router", "state", "experts")


@jax.tree_util.register_pytree_node_class
class Weights(dict):
    """The weights as a pytree whose static part carries what no shape
    tells: (top_k, routed_scaling_factor, norm_topk_prob, rms_norm_eps,
    kv heads, KDA heads, negative eigenvalues allowed)."""

    def __init__(self, leaves, hyper):
        super().__init__(leaves)
        self.hyper = tuple(hyper)

    def tree_flatten(self):
        return (dict(self),), self.hyper

    @classmethod
    def tree_unflatten(cls, hyper, children):
        return cls(children[0], hyper)


def hyper_of(cfg):
    return (int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]),
            bool(cfg.get("norm_topk_prob", True)),
            float(cfg["rms_norm_eps"]), int(cfg["num_key_value_heads"]),
            int(cfg["linear_attn_config"]["num_heads"]),
            bool(cfg.get("kda_allow_neg_eigval", True)))


def routed_width(cfg):
    """The router's outputs: the published count of experts, of which
    ``n_routed_experts`` are held here."""
    return int(cfg.get("router_experts", cfg["n_routed_experts"]))


def is_gqa(cfg, i):
    return i in [int(n) for n in cfg["gqa_layers"]]


def layer_shapes(cfg, i):
    """{leaf: shape} of layer `i`: its token mixer, then the router over
    every expert, the HELD experts and the shared expert."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    d, kv = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    held, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    sf = int(cfg["n_shared_experts"]) * f
    out = {"ln1": (h,)}
    if is_gqa(cfg, i):
        out.update(wq=(h, heads * d), wk=(h, kv * d), wv=(h, kv * d),
                   wg=(h, heads * d), wo=(heads * d, h))
    else:
        lin = cfg["linear_attn_config"]
        kh, kd = int(lin["num_heads"]), int(lin["head_dim"])
        rank = int(cfg["kda_gate_rank"])
        width = kh * kd
        out.update(wqkv=(h, 3 * width),
                   conv=(int(lin["short_conv_kernel_size"]), 3 * width),
                   wf1=(h, rank), wf2=(rank, width), wg1=(h, rank),
                   wg2=(rank, width), wbeta=(h, kh), a_log=(kh,),
                   dt_bias=(width,), o_norm=(kd,), kwo=(width, h))
    out.update(ln2=(h,), router=(h, routed_width(cfg)),
               bias=(routed_width(cfg),), e_gate=(held, h, f),
               e_up=(held, h, f), e_down=(held, f, h), s_gate=(h, sf),
               s_up=(h, sf), s_down=(sf, h))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _uniform(key, shape, lo, hi, dtype, through):
    """U(lo, hi) put through ``log`` or the inverse of softplus."""
    u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    u = jnp.log(u) if through == "log" else u + jnp.log(-jnp.expm1(-u))
    return u.astype(dtype)


def init_weights(cfg, positions, seed, dtype=jnp.bfloat16):
    """Every weight from the seed, born on the device in the dtype it is
    served in, ONE LEAF A PROGRAM.  Normal(0, initializer_range) matrices
    (embedding, head and convolution too), unit RMSNorm gains, a
    Normal(0, 0.1) selection bias per router (from the configuration's
    own ``router_bias_seed``, the same for every seed), ``A_log = log
    U(1, 16)`` and ``dt_bias = softplus^-1 U(1e-3, 0.1)``.  Nothing has a
    table of positions, so `positions` changes no weight."""
    del positions
    dtype = jnp.dtype(dtype)
    std = float(cfg["initializer_range"])
    root = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    # which experts a router's bias makes popular sets how many of the
    # HELD ones a step touches, the amount of work: like the traffic's
    # lengths it is ONE realisation for every seed
    biases = jax.random.PRNGKey(int(cfg["router_bias_seed"]))
    count = iter(range(1 << 30))

    def leaf(name, shape):
        if name in ("ln1", "ln2", "o_norm", "norm_f"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(biases if name == "bias" else root,
                                 next(count))
        if name == "a_log":
            return _uniform(key, shape, 1.0, 16.0, dtype, "log")
        if name == "dt_bias":
            return _uniform(key, shape, 1e-3, 0.1, dtype, "softplus^-1")
        return _normal(key, shape, BIAS_STD if name == "bias" else std,
                       dtype)

    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layers = [{k: leaf(k, s) for k, s in layer_shapes(cfg, i).items()}
              for i in range(int(cfg["num_hidden_layers"]))]
    return Weights({"embed": leaf("embed", (v, h)), "layers": layers,
                    "norm_f": leaf("norm_f", (h,)),
                    "head": leaf("head", (h, v))}, hyper_of(cfg))


def to_program(weights, cfg):
    """{program parameter name: array}: the same arrays, renamed."""
    out = {name: weights[k] for k, name in TOP.items()}
    for i, lp in enumerate(weights["layers"]):
        for k, a in lp.items():
            pat = COMMON.get(k) or GQA.get(k) or KDA[k]
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


def _of(precision, part):
    """The precision `part` (one of PARTS, or "rest") is computed in
    under `precision`, which is one for all ("fp8") or one for a single
    part ("fp8:state": the rest float32)."""
    lower, _, only = precision.partition(":")
    if only not in ("",) + PARTS:
        raise ValueError(f"unknown reference precision {precision!r}")
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


def _gqa(a, lp, heads, hyper, precision):
    kv = hyper[4]
    precision = _of(precision, "rest")
    q_ = lambda t: _q(t, precision)
    t = a.shape[0]
    d = lp["wq"].shape[1] // heads
    q = _linear(a, lp["wq"], precision).reshape(t, heads, d)
    k = _linear(a, lp["wk"], precision).reshape(t, kv, d)
    v = _linear(a, lp["wv"], precision).reshape(t, kv, d)
    group = heads // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    def rows(start):
        """One block of query rows against every key; q head h reads kv
        head h // group."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        qb = qb.reshape(block, kv, group, d)
        s = jnp.einsum("qngd,knd->ngqk", qb, k, precision="highest") * scale
        seen = jnp.arange(t)[None, :] <= (start
                                          + jnp.arange(block))[:, None]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", q_(jax.nn.softmax(s, -1)), v,
                       precision="highest")
        return o.reshape(block, heads * d)

    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence of {t} is no multiple of {block}")
    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * d)
    gate = jax.nn.sigmoid(_linear(a, lp["wg"], precision))
    return _linear(q_(gate * q_(o)), lp["wo"], precision)


def _kda(a, lp, hyper, precision):
    eps, heads, neg = hyper[3], hyper[5], hyper[6]
    held_in = _of(precision, "state")
    precision = _of(precision, "rest")
    q_ = lambda t: _q(t, precision)
    state_ = lambda t: _q(t, held_in)
    t = a.shape[0]
    taps, width3 = lp["conv"].shape
    d = width3 // (3 * heads)
    # what the convolution reads is what the program's tail holds
    x = state_(_linear(a, lp["wqkv"], precision))
    padded = jnp.concatenate([jnp.zeros((taps - 1, width3), x.dtype), x], 0)
    mixed = sum(padded[j:j + t] * lp["conv"][j] for j in range(taps))
    mixed = q_(jax.nn.silu(mixed)).reshape(t, 3, heads, d)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + L2_EPS)

    q = unit(mixed[:, 0]) * (d ** -0.5)
    k = unit(mixed[:, 1])
    v = mixed[:, 2]
    f = _linear(_linear(a, lp["wf1"], precision), lp["wf2"], precision)
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        f + lp["dt_bias"]).reshape(t, heads, d)
    beta = jax.nn.sigmoid(_linear(a, lp["wbeta"], precision))
    if neg:
        beta = 2.0 * beta

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hc,hcd->hd", kt, s,
                                           precision="highest"))
        s = state_(s + kt[..., None] * u[:, None, :])
        return s, jnp.einsum("hc,hcd->hd", qt, s, precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms_norm(q_(o), lp["o_norm"], eps).reshape(t, heads * d)
    gate = jax.nn.sigmoid(_linear(_linear(a, lp["wg1"], precision),
                                  lp["wg2"], precision))
    return _linear(q_(q_(o) * gate), lp["kwo"], precision)


def _experts(m, lp, hyper, precision):
    """(the layer's output [T, H] from the held experts and the shared
    one, the margin [T] by which the last expert picked leads the first
    left out)."""
    top_k, route_scale, norm_topk = hyper[:3]
    gate_in = _of(precision, "router")
    q_ = lambda t: _q(t, _of(precision, "rest"))
    precision = _of(precision, "experts")
    s = _q(jax.nn.sigmoid(_linear(m, lp["router"], gate_in)), gate_in)
    n = s.shape[-1]                                              # [T, E]
    lead, picked = jax.lax.top_k(s + lp["bias"], min(top_k + 1, n))
    margin = lead[:, top_k - 1] - lead[:, top_k] if n > top_k \
        else jnp.full(s.shape[:1], jnp.inf)
    mask = jax.nn.one_hot(picked[:, :top_k], n, dtype=s.dtype).sum(1)
    w = s * mask
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * route_scale
    held = lp["e_gate"].shape[0]        # the share: experts [0, held)

    def one(y, ew):
        gate, up, down, we = _f32(ew)
        return y + we[:, None] * _swiglu(m, gate, up, down, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (lp["e_gate"], lp["e_up"], lp["e_down"],
                         w[:, :held].T))
    return q_(y) + _swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"],
                           precision), margin


def hidden_fn(weights, ids, heads, precision="float32"):
    """[T] token ids -> ([T, H] hidden states after the final norm, [T]
    the narrowest margin of a position's picks over the layers)."""
    hyper = weights.hyper
    eps = hyper[3]
    rest = _of(precision, "rest")
    q_ = lambda t: _q(t, rest)
    margin = jnp.full(ids.shape, jnp.inf)
    x = q_(weights["embed"][ids].astype(jnp.float32))
    for lp in weights["layers"]:
        small = _f32({k: a for k, a in lp.items() if a.ndim < 3})
        a = q_(_rms_norm(x, small["ln1"], eps))
        mixer = _gqa(a, small, heads, hyper, precision) if "wq" in lp \
            else _kda(a, small, hyper, precision)
        x = q_(x + mixer)
        m = q_(_rms_norm(x, small["ln2"], eps))
        # the experts' stacks stay in their storage dtype until the loop
        # reaches each expert (a cast up changes no number)
        big = {k: a for k, a in lp.items() if a.ndim == 3}
        y, led = _experts(m, {**small, **big}, hyper, precision)
        x, margin = q_(x + y), jnp.minimum(margin, led)
    return q_(_rms_norm(x, weights["norm_f"].astype(jnp.float32),
                        eps)), margin


def logits_fn(weights, ids, heads, precision="float32"):
    """[B, S] token ids -> [B, S, V] float32 logits (small sizes: the
    whole matrix at once)."""
    head = weights["head"].astype(jnp.float32)
    return jnp.stack([_linear(hidden_fn(weights, row, heads, precision)[0],
                              head, _of(precision, "rest")) for row in ids])


@functools.partial(jax.jit, static_argnums=(2,))
def undecided(weights, tokens, heads):
    """[1, T] tokens -> [T] bool: the positions one of whose picks is
    within ROUTE_TIE of a tie in the float32 pass, which carry no
    verdict."""
    return hidden_fn(weights, tokens[0], heads)[1] < ROUTE_TIE


def next_token_gaps(weights, tokens, chosen, heads, precision="float32"):
    """For one sequence `tokens` [1, T] and the token `chosen` [T] that
    followed each position: (best logit, chosen token's logit, argmax)
    per position, from the full forward pass, the head one block of
    positions at a time.  At a position whose routing this pass leaves
    `undecided`, the chosen token's logit is reported as the best: no
    verdict, a gap of 0 (the module's departures).  The float32 pass
    says on standard error how many of the served positions (the run
    from the first to the last token chosen) were left so."""
    best, took, arg, tied = _gaps(weights, tokens, chosen, heads, precision)
    if precision == "float32":
        served = np.flatnonzero(np.asarray(chosen))
        if len(served):
            lo, hi = served[0], served[-1] + 1
            left = int(np.asarray(tied)[lo:hi].sum())
            print(f"solar_open2 reference: {hi - lo} served positions, "
                  f"{left} within ROUTE_TIE of a tie and left uncompared "
                  f"({100.0 * left / (hi - lo):.1f}%)", file=sys.stderr,
                  flush=True)
    return best, took, arg


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(weights, tokens, chosen, heads, precision):
    x, margin = hidden_fn(weights, tokens[0], heads, precision)
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
