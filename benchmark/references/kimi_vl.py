"""Plain reference of the language model of Kimi-VL-A3B-Instruct
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json;
the decoder is DeepSeek-V3's, arXiv:2412.19437 section 2.1): pre-norm
residual blocks under RMSNorm, multi-head latent attention without a
query rank, a leading dense SwiGLU layer, then layers of sigmoid-routed
SwiGLU experts with shared experts, a final RMSNorm and an untied head.

Per layer, for ``x`` [T, H] and ``a = RMSNorm(x)``:

* attention: ``q = a W_q`` -> per head ``[q_nope | q_rope]``;
  ``a W_kva`` -> ``[c | k_rope]``; ``c <- RMSNorm(c)``; per head
  ``[k_nope | v] = c W_kvb``.  RoPE (theta from the configuration, no
  scaling) turns ``q_rope`` of every head and the ONE ``k_rope`` that all
  heads share.  **Pairing convention: interleaved** -- dimensions
  ``(2i, 2i + 1)`` of the rotary part form pair ``i`` with frequency
  ``theta ** (-2i / d_rope)``, as the published checkpoints store them
  (the published code de-interleaves and then rotates halves, which is
  the same rotation under a permutation shared by q and k);
  ``to_program`` hands the program the same columns and the program
  rotates the same pairs.  Scores ``(q_nope k_nope + q_rope k_rope) /
  sqrt(d_nope + d_rope)``, causal softmax, ``o = sum p v``, output
  ``o W_o``.  The EXPANDED form over the full sequence: K and V are
  materialised for every position, nothing is cached or absorbed.
* dense layers (the first ``first_k_dense_replace``):
  ``(silu(m W_gate) * (m W_up)) W_down`` on ``m = RMSNorm(x)``.
* expert layers: ``s = sigmoid(m W_g)`` [E]; the ``top_k`` largest of
  ``s + b`` are picked (``b`` only picks, it never weighs; one group);
  ``w = s_picked / sum(s_picked) * routed_scaling_factor``;
  ``y = sum_e w_e SwiGLU_e(m) + SwiGLU_shared(m)``.  Every expert is
  evaluated on every position under a mask of zero weights: no sort, no
  grouped product, no capacity, nothing dropped.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision;
it imports nothing of the program and takes nothing the program made.

Departures from the published description, each for memory or time only:
* weights are stored in bfloat16 (the dtype the configuration serves in)
  and cast up where they are used; they are made leaf by leaf, because
  one program's float32 temporaries for 3.1 B parameters do not fit
  beside the model on a 16 GB chip;
* attention runs one block of query rows at a time against every key
  (16 heads x 16,384^2 float32 scores are 17 GB), the experts one at a
  time, the head one block of positions at a time (16,384 x 163,840
  float32 logits are 10.7 GB); no block changes a number;
* the router's product and sigmoid are float32 like everything here; the
  published implementation computes its gate in float32 too.

And one in what is compared: **a position whose routing is within
rounding distance of a tie carries no verdict.**  The pick of ``top_k``
experts is a step in the scores: where the last expert taken and the
first left out lie closer than ``ROUTE_TIE`` (two steps of bfloat16 at a
score of 0.5 to 1) in any expert layer, activations held in bfloat16, the
precision the configuration states, cannot decide the pick, the other
pick is as sound an answer, and this float32 pass is one of two.  With
every weight drawn at 0.02 one such flip moves the logits as far as the
fp8 control does (PERF.md section 2), so ``next_token_gaps`` reports a
gap of 0 there (``took = best``) and every other position is held to the
float32 pass; ``undecided`` says which positions those are.  The
harness's own training check leaves out what is nought to rounding in
the same way (``check.DEAD_GRADIENT``).

``precision``: ``"float32"`` is the reference; ``"fp8"`` is the control
as ``references/gpt.py`` defines it: every tensor the bfloat16 program
rounds (weights and activations entering a product, every layer's
output, the latent rows, the router's scores, the residual stream, the
logits) cut to fp8's e4m3 significand.  ``"fp8:router"``,
``"fp8:latent"`` and ``"fp8:experts"`` are the controls of one part:
that part alone is cut (the router's product and scores; the cached rows
``c`` and ``k_rope``; the routed and shared experts' products), the rest
stays float32.  ``"bfloat16"`` in fp8's place cuts to its significand.
"""
import functools

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024        # query rows / head positions computed at a time
# reference leaf of a layer -> the program's parameter name ({i} = layer)
ATTN = {
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wkva": "model.layers.{i}.self_attn.kv_a_proj.weight",
    "kv_norm": "model.layers.{i}.self_attn.kv_a_layernorm.weight",
    "wkvb": "model.layers.{i}.self_attn.kv_b_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
}
DENSE = {
    "gate": "model.layers.{i}.mlp.gate_proj.weight",
    "up": "model.layers.{i}.mlp.up_proj.weight",
    "down": "model.layers.{i}.mlp.down_proj.weight",
}
MOE = {
    "router": "model.layers.{i}.mlp.gate_weight",
    "bias": "model.layers.{i}.mlp.score_bias",
    "e_gate": "model.layers.{i}.mlp.w_gate",
    "e_up": "model.layers.{i}.mlp.w_up",
    "e_down": "model.layers.{i}.mlp.w_down",
    "s_gate": "model.layers.{i}.mlp.shared_gate",
    "s_up": "model.layers.{i}.mlp.shared_up",
    "s_down": "model.layers.{i}.mlp.shared_down",
}
TOP = {"embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
       "head": "lm_head.weight"}
BIAS_STD = 0.1          # the seeded, NON-zero selection bias of a router
# a pick whose margin over the first expert left out is under this is one
# that bfloat16 activations cannot decide: two bfloat16 steps (2 x 2**-8)
# of a score between 0.5 and 1, where the picked `s + b` lie
ROUTE_TIE = 2.0 ** -7
SIGNIFICAND = {"fp8": 3, "bfloat16": 7}     # explicit bits of a control


@jax.tree_util.register_pytree_node_class
class Weights(dict):
    """The weights as a pytree whose static part carries what no shape
    tells: (top_k, routed_scaling_factor, norm_topk_prob, rope_theta,
    rms_norm_eps, qk_rope_head_dim, v_head_dim)."""

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
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))


def layer_shapes(cfg, i):
    """{leaf: shape} of layer `i`: attention, then the dense feed-forward
    or the router, its experts and the shared experts."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    lora, v = int(cfg["kv_lora_rank"]), int(cfg["v_head_dim"])
    out = {"ln1": (h,), "wq": (h, heads * (nope + rope)),
           "wkva": (h, lora + rope), "kv_norm": (lora,),
           "wkvb": (lora, heads * (nope + v)), "wo": (heads * v, h),
           "ln2": (h,)}
    if i < int(cfg["first_k_dense_replace"]):
        f = int(cfg["intermediate_size"])
        out.update(gate=(h, f), up=(h, f), down=(f, h))
    else:
        e, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
        sf = int(cfg["n_shared_experts"]) * f
        out.update(router=(h, e), bias=(e,), e_gate=(e, h, f),
                   e_up=(e, h, f), e_down=(e, f, h), s_gate=(h, sf),
                   s_up=(h, sf), s_down=(sf, h))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_weights(cfg, positions, seed, dtype=jnp.bfloat16):
    """Every weight from the seed, born on the device in the dtype it is
    served in, ONE LEAF A PROGRAM (see the module's departures).
    Normal(0, initializer_range) matrices (the token embedding and the
    head too), unit RMSNorm gains, and a Normal(0, 0.1) selection bias
    per router: non-zero, so that "the bias picks and never weighs" is
    exercised.  RoPE has no table, so `positions` changes no weight."""
    del positions
    dtype = jnp.dtype(dtype)
    std = float(cfg["initializer_range"])
    root = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    count = iter(range(1 << 30))

    def leaf(name, shape):
        if name in ("ln1", "ln2", "kv_norm", "norm_f"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(root, next(count))
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
            pat = ATTN.get(k) or DENSE.get(k) or MOE[k]
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
    """The precision `part` ("router", "latent", "experts" or "rest") is
    computed in under `precision`, which is one for all ("fp8") or one
    for a single part ("fp8:latent": the rest float32)."""
    lower, _, only = precision.partition(":")
    if only not in ("", "router", "latent", "experts"):
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


def _rope(x, positions, theta):
    """x [T, ..., d] rotated in interleaved pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv          # [T, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _swiglu(m, gate, up, down, precision):
    q_ = lambda t: _q(t, precision)
    return _linear(q_(jax.nn.silu(_linear(m, gate, precision))
                      * _linear(m, up, precision)), down, precision)


def _attention(a, lp, heads, hyper, precision):
    _, _, _, theta, eps, rope, vd = hyper
    precision, rows_in = _of(precision, "rest"), _of(precision, "latent")
    q_ = lambda t: _q(t, precision)
    row_ = lambda t: _q(q_(t), rows_in)        # what the pool would hold
    t = a.shape[0]
    lora = lp["kv_norm"].shape[0]
    nope = lp["wq"].shape[1] // heads - rope
    pos = jnp.arange(t)
    q = _linear(a, lp["wq"], precision).reshape(t, heads, nope + rope)
    q = q_(jnp.concatenate([q[..., :nope],
                            _rope(q[..., nope:], pos, theta)], -1))
    ckr = _linear(a, lp["wkva"], precision)
    c = row_(_rms_norm(ckr[:, :lora], lp["kv_norm"], eps))
    k_rope = row_(_rope(ckr[:, lora:], pos, theta))              # [T, rope]
    kv = _linear(c, lp["wkvb"], precision).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], (t, heads, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))

    def rows(start):
        """One block of query rows against every key."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") * scale
        seen = jnp.arange(t)[None, :] <= (start
                                          + jnp.arange(block))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, -1)), v,
                          precision="highest")

    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence of {t} is no multiple of {block}")
    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * vd)
    return _linear(q_(o), lp["wo"], precision)


def _experts(m, lp, hyper, precision):
    """(the layer's output [T, H], the margin [T] by which the last
    expert picked leads the first left out)."""
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

    def one(y, ew):
        gate, up, down, we = _f32(ew)
        return y + we[:, None] * _swiglu(m, gate, up, down, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (lp["e_gate"], lp["e_up"], lp["e_down"], w.T))
    return q_(y) + _swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"],
                           precision), margin


def hidden_fn(weights, ids, heads, precision="float32"):
    """[T] token ids -> ([T, H] hidden states after the final norm, [T]
    the narrowest margin of a position's picks over the expert layers)."""
    hyper = weights.hyper
    eps = hyper[4]
    rest = _of(precision, "rest")
    q_ = lambda t: _q(t, rest)
    margin = jnp.full(ids.shape, jnp.inf)
    x = q_(weights["embed"][ids].astype(jnp.float32))
    for lp in weights["layers"]:
        small = _f32({k: a for k, a in lp.items() if a.ndim < 3})
        a = q_(_rms_norm(x, small["ln1"], eps))
        x = q_(x + _attention(a, small, heads, hyper, precision))
        m = q_(_rms_norm(x, small["ln2"], eps))
        if "router" in lp:
            # the experts' stacks stay in their storage dtype until the
            # loop reaches each expert (a cast up changes no number)
            big = {k: a for k, a in lp.items() if a.ndim == 3}
            y, led = _experts(m, {**small, **big}, hyper, precision)
            x, margin = q_(x + y), jnp.minimum(margin, led)
        else:
            x = q_(x + _swiglu(m, small["gate"], small["up"],
                               small["down"], rest))
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


@functools.partial(jax.jit, static_argnums=(3, 4))
def next_token_gaps(weights, tokens, chosen, heads, precision="float32"):
    """For one sequence `tokens` [1, T] and the token `chosen` [T] that
    followed each position: (best logit, chosen token's logit, argmax)
    per position, from the full forward pass, the head one block of
    positions at a time.  At a position whose routing this pass leaves
    `undecided`, the chosen token's logit is reported as the best: no
    verdict, a gap of 0 (the module's departures)."""
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
    return best, jnp.where(margin < ROUTE_TIE, best, took), arg.reshape(t)
