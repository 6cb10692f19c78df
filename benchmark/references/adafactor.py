"""Plain Adafactor (Shazeer & Stern 2018, arXiv:1804.04235, algorithm 4
without momentum and without the relative step size) as the training
configurations state it: factored second moments for matrices, update
clipping at d, step scaled by max(eps2, RMS(parameter)), decay
1 - t^-0.8.  Float32 arithmetic on one leaf; the caller rounds the new
parameter to its storage dtype, since the configurations keep no float32
master copy.
"""
import jax.numpy as jnp

EPS1, EPS2, CLIP, DECAY = 1e-30, 1e-3, 1.0, 0.8


def init_leaf(p):
    if p.ndim >= 2:
        return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
    return {"v": jnp.zeros(p.shape, jnp.float32)}


def update_leaf(g, p, state, lr, t):
    """One step on one leaf (float32 in, float32 out)."""
    beta2 = 1.0 - jnp.power(t, -DECAY)
    g2 = jnp.square(g) + EPS1
    if "vr" in state:
        vr = beta2 * state["vr"] + (1 - beta2) * jnp.mean(g2, -1)
        vc = beta2 * state["vc"] + (1 - beta2) * jnp.mean(g2, -2)
        new = {"vr": vr, "vc": vc}
        v_hat = (vr[..., :, None] * vc[..., None, :]
                 / jnp.maximum(jnp.mean(vr, -1), 1e-30)[..., None, None])
    else:
        v_hat = beta2 * state["v"] + (1 - beta2) * g2
        new = {"v": v_hat}
    u = g / jnp.sqrt(v_hat)
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(jnp.square(u))) / CLIP)
    scale = jnp.maximum(EPS2, jnp.sqrt(jnp.mean(jnp.square(p))))
    return p - lr * scale * u, new


def grad_norm_from_state(state, shape):
    """Norm of the FIRST gradient a leaf's optimizer state saw, worked
    out from that state after step 1 (decay is 0 at t = 1, so the second
    moments are the squared gradient's means plus eps1)."""
    if "vr" in state:
        return jnp.sqrt(jnp.sum(state["vr"]) * shape[-1])
    return jnp.sqrt(jnp.sum(state["v"]))
