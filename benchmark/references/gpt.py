"""Plain reference of the GPT-3 decoder (arXiv:2005.14165, section 2.1) as
PaddleNLP's gpt3 presets ship it: learned positions, pre-LayerNorm blocks,
fused qkv projection, tanh-GELU feed-forward of width 4h, output head tied
to the token embedding, mean cross entropy over every position.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no cache, no batching tricks.  It imports nothing of the
program and takes nothing the program made: weights come from
:func:`init_weights` and the seed, and :func:`to_program` names them the
way the program's ``state_dict`` does so that the harness can hand the
SAME values to the system under test.

Departures from a textbook forward, each for memory only:
* layers are stacked on a leading axis and walked with ``lax.scan`` under
  ``jax.checkpoint`` (recomputation changes no number);
* weights are stored in the dtype the configuration trains and serves in
  (bfloat16) and cast up layer by layer; gradients leave in that storage
  dtype too, as the configuration's optimizer receives them.

``precision``: ``"float32"`` is the reference.  ``"fp8"`` is the control
of "How correct is decided": the same computation one precision below the
configuration's bfloat16 -- every tensor the bfloat16 program rounds
(weights and activations entering a product, every layer's output, the
residual stream, the logits) is rounded to fp8's e4m3 significand (3
stored bits) on the way forward and its cotangent to e5m2's (2 bits) on
the way back, as fp8 training recipes do.  Only the significand is cut;
the exponent keeps float32's range, as a well-scaled fp8 path would see
to, so the control is the kindest fp8 there is.
"""
import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
STACKED = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
           "ln2_w", "ln2_b", "fc_in_w", "fc_in_b", "fc_out_w", "fc_out_b")
# reference leaf -> the program's parameter name ({i} = layer)
NAMES = {
    "wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
    "ln1_w": "gpt.h.{i}.ln_1.weight", "ln1_b": "gpt.h.{i}.ln_1.bias",
    "qkv_w": "gpt.h.{i}.attn.qkv_proj.weight",
    "qkv_b": "gpt.h.{i}.attn.qkv_proj.bias",
    "out_w": "gpt.h.{i}.attn.out_proj.weight",
    "out_b": "gpt.h.{i}.attn.out_proj.bias",
    "ln2_w": "gpt.h.{i}.ln_2.weight", "ln2_b": "gpt.h.{i}.ln_2.bias",
    "fc_in_w": "gpt.h.{i}.mlp.fc_in.weight",
    "fc_in_b": "gpt.h.{i}.mlp.fc_in.bias",
    "fc_out_w": "gpt.h.{i}.mlp.fc_out.weight",
    "fc_out_b": "gpt.h.{i}.mlp.fc_out.bias",
    "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias",
}


def sizes(cfg, positions):
    """(L, H, heads, F, V, P) of a configuration file's ``model`` group."""
    h = int(cfg["hidden_size"])
    return (int(cfg["num_hidden_layers"]), h, int(cfg["num_attention_heads"]),
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]),
            int(positions))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init(dims, std, dtype, key):
    L, H, _, F, V, P = dims
    ks = jax.random.split(key, 6)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)
    zeros = lambda *s: jnp.zeros(s, dtype)
    return {
        "wte": normal(ks[0], (V, H)), "wpe": normal(ks[1], (P, H)),
        "ln1_w": ones(L, H), "ln1_b": zeros(L, H),
        "qkv_w": normal(ks[2], (L, H, 3 * H)), "qkv_b": zeros(L, 3 * H),
        "out_w": normal(ks[3], (L, H, H)), "out_b": zeros(L, H),
        "ln2_w": ones(L, H), "ln2_b": zeros(L, H),
        "fc_in_w": normal(ks[4], (L, H, F)), "fc_in_b": zeros(L, F),
        "fc_out_w": normal(ks[5], (L, F, H)), "fc_out_b": zeros(L, H),
        "lnf_w": ones(H), "lnf_b": zeros(H),
    }


def init_weights(cfg, positions, seed, dtype=jnp.bfloat16):
    """Every weight of the model in ONE jitted program from the seed, born
    on the device in the dtype it is trained and served in.  Normal(0,
    initializer_range) matrices and embeddings, unit LayerNorm gains, zero
    biases: the published initialisation."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return _init(sizes(cfg, positions), float(cfg["initializer_range"]),
                 jnp.dtype(dtype), key)


def leaf_names(cfg):
    """Program parameter names in the order :func:`leaf_values` flattens."""
    L = int(cfg["num_hidden_layers"])
    out = []
    for k, pat in NAMES.items():
        out += [pat.format(i=i) for i in range(L)] if k in STACKED else [pat]
    return out


def to_program(weights, cfg):
    """{program parameter name: array} -- the stacked layers split up."""
    out = {}
    for k, pat in NAMES.items():
        if k in STACKED:
            for i in range(weights[k].shape[0]):
                out[pat.format(i=i)] = weights[k][i]
        else:
            out[pat] = weights[k]
    return out


def leaf_values(per_leaf):
    """Flatten {leaf: scalar or [L] vector} in :func:`leaf_names` order."""
    return jnp.concatenate([jnp.atleast_1d(per_leaf[k]) for k in NAMES])


# ----------------------------------------------------------------- forward
def _round_significand(x, bits):
    """x rounded to `bits` stored significand bits (nearest)."""
    m, e = jnp.frexp(x)
    scale = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


@jax.custom_vjp
def _fp8(x):
    return _round_significand(x, 3)


_fp8.defvjp(lambda x: (_round_significand(x, 3), None),
            lambda _, g: (_round_significand(g, 2),))


def _q(x, precision):
    """The rounding the control applies wherever the bfloat16 program
    rounds; the identity for the reference."""
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8(x)
    raise ValueError(f"unknown reference precision {precision!r}")


def _linear(x, w, precision):
    """x [..., K] @ w [K, N], accumulated exactly in float32."""
    return _q(jnp.matmul(_q(x, precision), _q(w, precision),
                         precision="highest"), precision)


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _block(x, lp, heads, precision):
    b, s, h = x.shape
    d = h // heads
    q_ = lambda t: _q(t, precision)
    a = q_(_layer_norm(x, lp["ln1_w"], lp["ln1_b"]))
    qkv = q_(_linear(a, lp["qkv_w"], precision) + lp["qkv_b"])
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, d), 2, 0)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = q_(jnp.einsum("bhqk,bkhd->bqhd",
                        q_(jax.nn.softmax(scores, -1)), v,
                        precision="highest").reshape(b, s, h))
    x = q_(x + _linear(att, lp["out_w"], precision) + lp["out_b"])
    m = q_(_layer_norm(x, lp["ln2_w"], lp["ln2_b"]))
    m = q_(jax.nn.gelu(_linear(m, lp["fc_in_w"], precision)
                       + lp["fc_in_b"], approximate=True))
    return q_(x + _linear(m, lp["fc_out_w"], precision) + lp["fc_out_b"])


def logits_fn(weights, ids, heads, precision="float32"):
    """[B, S] token ids -> [B, S, V] float32 logits."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    wte = f32(weights["wte"])
    s = ids.shape[1]
    x = _q(wte[ids] + f32(weights["wpe"])[:s][None], precision)

    @jax.checkpoint
    def body(x, lp):
        return _block(x, f32(lp), heads, precision), None

    x, _ = jax.lax.scan(body, x, {k: weights[k] for k in STACKED})
    x = _q(_layer_norm(x, f32(weights["lnf_w"]), f32(weights["lnf_b"])),
           precision)
    return _linear(x, wte.T, precision)


def loss_fn(weights, ids, labels, heads, precision="float32"):
    """Mean cross entropy of every position's logits against `labels`."""
    logp = jax.nn.log_softmax(logits_fn(weights, ids, heads, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@functools.partial(jax.jit, static_argnums=(3, 4))
def next_token_gaps(weights, tokens, chosen, heads, precision="float32"):
    """For one sequence `tokens` [1, T] and the token `chosen` [T] that
    followed each position: (best logit, chosen token's logit, argmax)
    per position, from the full forward pass."""
    logits = logits_fn(weights, tokens, heads, precision)[0]
    best = jnp.max(logits, -1)
    took = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
    return best, took, jnp.argmax(logits, -1)
