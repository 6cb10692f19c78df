"""The reference's first training steps: loss, gradient, plain Adafactor,
round to the storage dtype, again -- for any model reference that offers
``init_weights``, ``loss_fn``, ``STACKED``, ``NAMES`` and ``leaf_values``.

Returns the readings that "How correct is decided" compares: each step's
loss, every leaf's first-gradient norm and every leaf's change after the
steps.  ``fault="half_batch"`` trains on the first half of every batch
(the mean taken over the rest): a fault read with the reference in the
program's place.
"""
import functools

import jax
import jax.numpy as jnp

from benchmark.references import adafactor


def _per_leaf(ref, fn, *trees):
    """Apply fn(leaf arrays...) per leaf; stacked leaves layer by layer."""
    out = {}
    for k in ref.NAMES:
        args = [t[k] for t in trees]
        out[k] = jax.vmap(fn)(*args) if k in ref.STACKED else fn(*args)
    return out


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def init_state(ref, weights):
    return _per_leaf(ref, adafactor.init_leaf, weights)


@functools.partial(jax.jit, static_argnums=(0, 6, 7), donate_argnums=(1, 2))
def _step(ref, weights, state, ids, labels, lr_t, heads, precision):
    lr, t = lr_t
    loss, grads = jax.value_and_grad(ref.loss_fn)(
        weights, ids, labels, heads, precision)
    gnorm = _per_leaf(ref, _norm, grads)

    def upd(g, p, s):
        new_p, new_s = adafactor.update_leaf(
            g.astype(jnp.float32), p.astype(jnp.float32), s, lr, t)
        return new_p.astype(p.dtype), new_s

    new_w, new_s = {}, {}
    for k in ref.NAMES:
        f = jax.vmap(upd) if k in ref.STACKED else upd
        new_w[k], new_s[k] = f(grads[k], weights[k], state[k])
    return loss, new_w, new_s, ref.leaf_values(gnorm)


@functools.partial(jax.jit, static_argnums=(0,))
def change_norms(ref, after, before):
    return ref.leaf_values(_per_leaf(
        ref, lambda a, b: _norm(a.astype(jnp.float32)
                                - b.astype(jnp.float32)), after, before))


def first_steps(ref, cfg, positions, seed, batches, lr, precision="float32",
                fault=None):
    """`batches` [n, B, S + 1] token ids; returns the readings as numpy."""
    import numpy as np
    heads = int(cfg["num_attention_heads"])
    weights = ref.init_weights(cfg, positions, seed)
    state = init_state(ref, weights)
    losses, gnorm1 = [], None
    for i, rows in enumerate(np.asarray(batches)):
        if fault == "half_batch":
            rows = rows[: len(rows) // 2]
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        loss, weights, state, gnorm = _step(
            ref, weights, state, jnp.asarray(rows[:, :-1]),
            jnp.asarray(rows[:, 1:]),
            (jnp.float32(lr), jnp.float32(i + 1)), heads, precision)
        losses.append(float(loss))
        if i == 0:
            gnorm1 = np.asarray(gnorm)
    change = change_norms(ref, weights,
                          ref.init_weights(cfg, positions, seed))
    return {"losses": losses, "grad_norms": gnorm1,
            "change_norms": np.asarray(change)}
