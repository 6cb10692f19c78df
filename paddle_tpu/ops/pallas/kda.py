"""The decode step of Kimi Delta Attention over the pool of recurrent
states, in place (the XLA form of op `kda_step` gathers the rows' states,
updates them and scatters them back: three passes over what is the second
largest stream of a decode step).

One grid step holds one row's state for `_HEADS` heads, [heads, dk, dv]
float32, read from the row's slot of the pool and written back to the
same block (`input_output_aliases`); the slot comes from the prefetched
`slots`, so the pool is never gathered.  Per head

    S~ = Diag(a) S;  u = beta (v - S~^T k);  S' = S~ + k u^T;  o = S'^T q

are elementwise products and sublane sums on the [dk, dv] tile: the
vector unit does all of it, the kernel is bound by the state's read and
write.  `a`, `k` and `q` weigh ROWS of the tile (dk lies on sublanes), so
they are wanted as columns: the three vectors of every head of the step
are stacked into one [128, 128] tile and transposed once.

A row that is not live writes back what it read.  The caller gives every
row a slot of its own (dead rows take the slots no live row holds), so no
two grid steps touch one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_HEADS = 16         # heads a grid step: 1 MiB of state in, 1 MiB out
KERNEL_NAME = "kda_decode_step"


def supports(q_shape, state_shape):
    """[R, H, 128] vectors over a [S, H, 128, 128] pool, the heads in
    whole groups of 8 sublanes."""
    if len(q_shape) != 3 or len(state_shape) != 4:
        return False
    heads, dk = q_shape[1], q_shape[2]
    return (dk == _LANES and tuple(state_shape[1:]) == (heads, dk, _LANES)
            and heads % 8 == 0)


def _head_block(heads):
    return _HEADS if heads % _HEADS == 0 else 8


def _kernel(slots_ref, live_ref, akq_ref, v_ref, beta_ref, s_ref, o_ref,
            s_out_ref, *, hb):
    r = pl.program_id(0)
    live = live_ref[r] > 0
    # rows [a | k | q] x heads of the step, padded to a square tile and
    # transposed: column j is vector j along dk
    akq = akq_ref[0].reshape(3 * hb, _LANES)
    cols = jnp.concatenate(
        [akq, jnp.zeros((_LANES - 3 * hb, _LANES), jnp.float32)], 0).T
    for h in range(hb):
        s0 = s_ref[0, h]                                    # [dk, dv]
        a = cols[:, h:h + 1]
        k = cols[:, hb + h:hb + h + 1]
        q = cols[:, 2 * hb + h:2 * hb + h + 1]
        sd = s0 * a
        u = beta_ref[0, h:h + 1, :] * (
            v_ref[0, h:h + 1, :] - jnp.sum(sd * k, axis=0, keepdims=True))
        s1 = sd + k * u
        o_ref[0, h:h + 1, :] = jnp.sum(s1 * q, axis=0, keepdims=True)
        s_out_ref[0, h] = jnp.where(live, s1, s0)


def kda_decode_step(q, k, v, g, beta, state, slots, live, interpret=False):
    """As op `kda_step`: (o [R, H, dv] float32, the pool updated in
    place).  `slots` must name a different slot for every row."""
    f32 = jnp.float32
    rows, heads, dk = q.shape
    dv = v.shape[-1]
    hb = _head_block(heads)
    # [R, H / hb, 3, hb, dk]: the decay, the key and the query of a
    # step's heads lie together
    akq = jnp.stack([jnp.exp(g.astype(f32)), k.astype(f32), q.astype(f32)],
                    axis=1).reshape(rows, 3, heads // hb, hb, dk)
    akq = jnp.swapaxes(akq, 1, 2).reshape(rows * (heads // hb), 3, hb, dk)
    beta = jnp.broadcast_to(beta.astype(f32)[..., None], (rows, heads, dv))
    groups = heads // hb
    vec = pl.BlockSpec((1, hb, dv), lambda r, j, slots, live: (r, j, 0))
    pool = pl.BlockSpec((1, hb, dk, dv),
                        lambda r, j, slots, live: (slots[r], j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, groups),
            in_specs=[
                pl.BlockSpec((1, 3, hb, dk),
                             lambda r, j, slots, live: (r * groups + j,
                                                        0, 0, 0)),
                vec, vec, pool],
            out_specs=[vec, pool]),
        out_shape=[jax.ShapeDtypeStruct((rows, heads, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the prefetched scalars: slots, live, akq, v,
        # beta, state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME, interpret=interpret,
    )(slots.astype(jnp.int32), live.astype(jnp.int32), akq,
      v.astype(f32), beta, state)
    return o, state
