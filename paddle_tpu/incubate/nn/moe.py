"""Mixture-of-Experts with expert parallelism (reference analog:
python/paddle/incubate/distributed/models/moe/moe_layer.py — MoELayer with
gshard/switch gates over an expert-parallel process group, dispatching via
NCCL all-to-all).

TPU-native design (GShard / Switch-Transformer recipe): the experts' weights
are STACKED on a leading expert axis ([E, d, f]) and sharded over the "ep"
mesh axis via PartitionSpec annotations; token dispatch/combine are dense
one-hot einsums with a static per-expert capacity, so the whole layer is a
fixed-shape XLA program — GSPMD turns the [tokens, ...] <-> [experts, ...]
einsums into the all-to-alls the reference issues by hand, and overlaps them
with the expert matmuls on ICI.  No dynamic shapes, no per-expert Python
loops: everything lands on the MXU.

Within each expert, the hidden dimension may additionally be sharded over
"mp" (expert tensor parallelism), composing ep x mp the way the reference
composes its expert group with Megatron mp groups.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...autograd import engine
from ...distributed import mesh as mesh_mod
from ...nn import initializer as I
from ...nn.layer import Layer
from ...ops.dispatch import call_raw
from ...tensor import Tensor


try:
    from jax.core import trace_state_clean as _trace_state_clean
except ImportError:  # not re-exported in every jax release
    from jax._src.core import trace_state_clean as _trace_state_clean


def _maybe_shard(a, *spec):
    """with_sharding_constraint if the mesh carries the referenced axes."""
    if not mesh_mod.has_mesh():
        return a
    axes = set(mesh_mod.get_mesh().axis_names)
    spec = tuple(s if (s in axes and mesh_mod.degree(s) > 1) else None
                 for s in spec)
    if all(s is None for s in spec):
        return a
    try:
        return jax.lax.with_sharding_constraint(a, mesh_mod.sharding(*spec))
    except Exception:  # inside shard_map / no-mesh trace: annotation-free
        return a


def _activation(name):
    return {"gelu": lambda h: jax.nn.gelu(h, approximate=True),
            "relu": jax.nn.relu,
            "silu": jax.nn.silu,
            "swish": jax.nn.silu}[name]


def moe_ffn_expert_choice(x, wg, w1, b1, w2, b2, *, capacity, act="gelu",
                          z_loss_weight=0.0):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT selects its
    top-`capacity` tokens by router score — perfectly load-balanced by
    construction, so there is no aux loss and no token-side dropping
    heuristics.  Same stacked-expert einsum compute path as moe_ffn.

    x [N, d]; returns (y [N, d], aux==0 unless z_loss_weight).
    """
    N = x.shape[0]
    C = capacity
    compute_dtype = x.dtype

    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)       # [N, E]
    scores = jax.nn.softmax(logits, axis=-1)
    # each expert picks its C best tokens
    vals, idx = jax.lax.top_k(scores.T, C)                        # [E, C]
    sel = jax.nn.one_hot(idx, N, dtype=compute_dtype)             # [E, C, N]
    xin = jnp.einsum("ecn,nd->ecd", sel, x)
    xin = _maybe_shard(xin, "ep", None, None)
    h = jnp.einsum("ecd,edf->ecf", xin, w1.astype(compute_dtype)) \
        + b1.astype(compute_dtype)[:, None, :]
    h = _maybe_shard(_activation(act)(h), "ep", None, "mp")
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(compute_dtype)) \
        + b2.astype(compute_dtype)[:, None, :]
    out = _maybe_shard(out, "ep", None, None)
    # combine: scatter each expert's outputs back weighted by its score
    y = jnp.einsum("ecn,ec,ecd->nd", sel, vals.astype(compute_dtype), out)
    aux = jnp.zeros((), jnp.float32)   # balanced by construction
    if z_loss_weight:                  # router z-loss still applies
        aux = z_loss_weight * jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return y, aux


def moe_ffn(x, wg, w1, b1, w2, b2, *, top_k, capacity, act="gelu",
            z_loss_weight=0.0):
    """Pure-jax MoE feed-forward on flattened tokens.

    x [N, d]; wg [d, E]; w1 [E, d, f]; b1 [E, f]; w2 [E, f, d]; b2 [E, d].
    Returns (y [N, d], aux_loss scalar fp32).

    Routing: top-k softmax gating with a static capacity C per expert
    (tokens beyond capacity are dropped — their combine weight is zero and
    the residual path carries them, as in GShard).  aux_loss is the
    load-balancing loss E * sum_e(mean_tokens(prob_e) * frac_tokens(top1==e))
    plus an optional router z-loss.
    """
    N, d = x.shape
    E = wg.shape[1]
    C = capacity
    compute_dtype = x.dtype

    # --- router (always fp32: small matmul, numerically sensitive) --------
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)       # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)

    remaining = probs
    fill = jnp.zeros((E,), jnp.float32)        # slots already taken
    combine = jnp.zeros((N, E, C), jnp.float32)
    denom = jnp.zeros((N,), jnp.float32)
    top1_mask = None
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                       # [N]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)           # [N, E]
        if top1_mask is None:
            top1_mask = mask
        remaining = remaining * (1.0 - mask)
        gate = (probs * mask).sum(-1)                              # [N]
        # position of each token within its expert's capacity buffer
        pos = (jnp.cumsum(mask, axis=0) - 1.0 + fill[None, :])
        pos_tok = (pos * mask).sum(-1)                             # [N]
        fill = fill + mask.sum(0)
        # one_hot of an out-of-range position is all-zero => overflow drops
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), C,
                              dtype=jnp.float32)                   # [N, C]
        part = mask[:, :, None] * slot[:, None, :]                 # [N, E, C]
        combine = combine + gate[:, None, None] * part
        denom = denom + gate * part.sum((1, 2))
    combine = combine / jnp.maximum(denom, 1e-9)[:, None, None]
    dispatch = (combine > 0.0).astype(compute_dtype)

    # --- load-balancing aux loss (GShard eq.(4) / Switch) ------------------
    me = probs.mean(axis=0)                                        # [E]
    ce = top1_mask.mean(axis=0)                                    # [E]
    aux = E * jnp.sum(me * ce)
    if z_loss_weight:
        aux = aux + z_loss_weight * jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2)

    # --- expert compute: [N,*] <-> [E,C,*] einsums become all-to-all over
    # "ep" under GSPMD; the ffn matmuls run per-expert on the MXU ----------
    xin = jnp.einsum("nec,nd->ecd", dispatch, x)
    xin = _maybe_shard(xin, "ep", None, None)
    h = jnp.einsum("ecd,edf->ecf", xin, w1.astype(compute_dtype)) \
        + b1.astype(compute_dtype)[:, None, :]
    h = _maybe_shard(_activation(act)(h), "ep", None, "mp")
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(compute_dtype)) \
        + b2.astype(compute_dtype)[:, None, :]
    out = _maybe_shard(out, "ep", None, None)
    y = jnp.einsum("nec,ecd->nd", combine.astype(compute_dtype), out)
    return y, aux


def moe_route(x, wg, bias, *, top_k, scoring="softmax", norm_topk=True,
              route_scale=1.0):
    """The router of the dropless layer: x [N, d], wg [d, E], bias [E] or
    None -> (picked [N, k] int32 expert ids, weights [N, k] float32).
    Scores (`scoring`: "softmax" | "sigmoid") are computed in float32;
    `bias` is a SELECTION bias: the top-k are taken of ``scores + bias``
    but weighed by the scores alone; `norm_topk` divides the picked
    scores by their sum; `route_scale` multiplies the result."""
    logits = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32),
                        precision="highest")
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    chosen_by = scores if bias is None else scores + bias.astype(jnp.float32)
    _, picked = jax.lax.top_k(chosen_by, top_k)
    w = jnp.take_along_axis(scores, picked, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return picked.astype(jnp.int32), w * route_scale


def moe_dropless(x, picked, weights, w_gate, w_up, w_down, first=None):
    """SwiGLU experts over routed tokens with NO capacity: every one of
    the N x k assignments is computed whatever the imbalance.

    x [N, d]; picked, weights [N, k] (from `moe_route`); w_gate, w_up
    [E, d, f]; w_down [E, f, d] -- the experts stacked on a leading axis,
    so that "the experts held here" is a slice of it.  Assignments are
    sorted by expert, the three products run as grouped products over the
    stacked weights (op `grouped_matmul`: each expert's contiguous rows
    against its own matrix; `jax.lax.ragged_dot`, or the tiled kernel of
    ops/pallas/ on TPU), then unsorted and combined by `weights`.
    Shapes are static ([N * k, ...] rows whatever the routing), so one
    program serves every routing.  Returns y [N, d] in x's dtype.

    `first` makes the layer ONE SHARE of an expert-parallel layer: the
    stacked weights are those of experts ``[first, first + E)`` of a
    wider router.  Assignments to other experts are sorted behind the
    local groups and belong to no group: the grouped products leave their
    rows out, and they add nothing to y (what the absent shares would
    add is theirs to compute)."""
    n, d = x.shape
    k = picked.shape[1]
    experts = w_gate.shape[0]
    flat = picked.reshape(-1)
    if first is not None:
        local = picked - first
        here = (local >= 0) & (local < experts)
        flat = jnp.where(here, local, experts).reshape(-1)
        weights = weights * here
    order = jnp.argsort(flat)                  # stable: by expert, by token
    rows = x[order // k]                       # [N * k, d]
    # (an index past the last group, another share's, is counted nowhere)
    sizes = jnp.bincount(flat, length=experts).astype(jnp.int32)
    h = jax.nn.silu(call_raw("grouped_matmul", rows, w_gate, sizes)) \
        * call_raw("grouped_matmul", rows, w_up, sizes)
    out = call_raw("grouped_matmul", h.astype(x.dtype), w_down, sizes)
    if first is not None:
        # a grouped product says nothing of the rows no group covers
        out = jnp.where((jnp.arange(n * k) < jnp.sum(sizes))[:, None],
                        out, 0)
    back = jnp.argsort(order)                  # the inverse permutation
    out = out[back].reshape(n, k, d)
    return jnp.einsum("nkd,nk->nd", out, weights.astype(out.dtype))


class DroplessMoE(Layer):
    """Routed SwiGLU experts without capacity or drops, with optional
    shared experts that every token passes through.

    `scoring`, `score_bias` (a selection bias that picks and never
    weighs), `norm_topk`, `route_scale` and `num_shared` are values of
    this one layer, not subclasses.  ``forward(x)`` returns y; with
    ``live`` (a bool mask over the flattened tokens) it returns
    ``(y, load)`` where ``load`` [E] int32 counts the live tokens each
    expert received (the serving engine's `experts_touched`).

    ``held=(first, count)`` makes the layer one chip's share of an
    expert-parallel layer: the router keeps its `num_experts` outputs and
    its `top_k`, the stacked weights are those of experts
    ``[first, first + count)`` alone, y is the shared experts' result plus
    the held experts' part for the assignments that fall on them, and
    ``load`` [count] counts over the held experts.  The layer runs
    without its exchange: nothing stands in for the absent shares."""

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 scoring="softmax", score_bias=False, norm_topk=True,
                 route_scale=1.0, num_shared=0, init_std=0.02,
                 dtype="float32", held=None):
        super().__init__(dtype=dtype)
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} > num_experts={num_experts}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = None if held is None else (int(held[0]), int(held[1]))
        if self.held and not (0 <= self.held[0] and self.held[1] >= 1
                              and sum(self.held) <= self.num_experts):
            raise ValueError(f"held={held} is no range of the "
                             f"{num_experts} experts")
        stacked = self.held[1] if self.held else self.num_experts
        self.scoring, self.norm_topk = scoring, bool(norm_topk)
        self.route_scale = float(route_scale)
        init = I.Normal(0.0, init_std)

        def param(*shape):
            return self.create_parameter(list(shape),
                                         default_initializer=init)

        self.gate_weight = param(d_model, num_experts)
        self.score_bias = self.create_parameter(
            [num_experts], is_bias=True,
            default_initializer=I.Constant(0.0)) if score_bias else None
        self.w_gate = param(stacked, d_model, d_hidden)
        self.w_up = param(stacked, d_model, d_hidden)
        self.w_down = param(stacked, d_hidden, d_model)
        self.num_shared = int(num_shared)
        if self.num_shared:
            width = self.num_shared * d_hidden
            self.shared_gate = param(d_model, width)
            self.shared_up = param(d_model, width)
            self.shared_down = param(width, d_model)

    def forward(self, x, live=None):
        shape = x.shape
        x2 = x.reshape([-1, shape[-1]])
        if self.score_bias is None:
            route, args = (lambda x_, wg, **kw: moe_route(x_, wg, None, **kw),
                           [x2, self.gate_weight])
        else:
            route, args = moe_route, [x2, self.gate_weight, self.score_bias]
        picked, weights = engine.apply(
            "moe_route", route, args,
            {"top_k": self.top_k, "scoring": self.scoring,
             "norm_topk": self.norm_topk, "route_scale": self.route_scale})
        y = engine.apply("moe_dropless", moe_dropless,
                         [x2, picked, weights, self.w_gate, self.w_up,
                          self.w_down],
                         {"first": self.held[0] if self.held else None})
        if self.num_shared:
            from ...nn import functional as F
            y = y + F.linear(F.silu(F.linear(x2, self.shared_gate))
                             * F.linear(x2, self.shared_up),
                             self.shared_down)
        y = y.reshape(list(shape))
        if live is None:
            return y
        first, experts = self.held or (0, self.num_experts)
        load = engine.apply(
            "moe_load",
            lambda p, l, first, experts: jnp.sum(
                (p[:, :, None] - first
                 == jnp.arange(experts, dtype=p.dtype))
                & l[:, None, None], axis=(0, 1), dtype=jnp.int32),
            [picked, live], {"first": first, "experts": experts})
        return y, load


class MoELayer(Layer):
    """Drop-in FFN replacement with E experts and top-k routing.

    Reference analog: MoELayer(gate={'type': 'gshard'|'switch'}, experts=...)
    in paddle.incubate.distributed.models.moe.  Here the per-expert FFNs are
    a single stacked parameter set annotated over the "ep" mesh axis (build
    the mesh with ``fleet``'s ``ep_degree`` or ``mesh.build_mesh(ep=...)``);
    the fleet engine places them like any other annotated parameter.

    top_k=1 is a Switch layer, top_k=2 the GShard default.
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, eval_capacity_factor=2.0,
                 activation="gelu", z_loss_weight=0.0, gate="top_k",
                 name=None):
        super().__init__()
        if gate not in ("top_k", "gshard", "switch", "expert_choice"):
            raise ValueError(f"unknown gate type {gate!r}")
        if gate == "switch":
            top_k = 1          # reference: a switch gate IS top-1 routing
        self.gate = "top_k" if gate in ("gshard", "switch") else gate
        if self.gate != "expert_choice" and top_k > num_experts:
            raise ValueError(f"top_k={top_k} > num_experts={num_experts}")
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.activation = activation
        self.z_loss_weight = z_loss_weight
        ep = "ep" if mesh_mod.degree("ep") > 1 else None
        mp = "mp" if mesh_mod.degree("mp") > 1 else None
        from jax.sharding import PartitionSpec as P
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.Normal(0.0, 0.02))
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=I.Normal(0.0, 0.02))
        self.w1.pspec = P(ep, None, mp)
        self.b1 = self.create_parameter(
            [num_experts, d_hidden], is_bias=True,
            default_initializer=I.Constant(0.0))
        self.b1.pspec = P(ep, mp)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=I.Normal(0.0, 0.02))
        self.w2.pspec = P(ep, mp, None)
        self.b2 = self.create_parameter(
            [num_experts, d_model], is_bias=True,
            default_initializer=I.Constant(0.0))
        self.b2.pspec = P(ep, None)
        # last forward's load-balancing loss (a live autograd Tensor); sum
        # into the training loss via paddle_tpu.incubate.nn.moe_aux_loss()
        object.__setattr__(self, "_aux_loss", None)

    def restore_aux_loss(self, aux):
        """Re-attach an aux loss computed across a trace boundary (e.g.
        returned through recompute's jax.checkpoint) — the ONE sanctioned
        writer of the private storage besides forward()."""
        object.__setattr__(self, "_aux_loss", aux)

    @property
    def aux_loss(self):
        # NOTE: an AttributeError escaping a property falls through to
        # Layer.__getattr__ and masks the real failure — keep this body
        # exception-free.
        t = self._aux_loss
        if t is None:
            return None
        # a Tracer surviving past its trace (the fleet/jit step already
        # retraced and returned) is stale — reading it would poison eager
        # graphs, so report "no aux available" instead
        if isinstance(t._array, jax.core.Tracer) and _trace_state_clean():
            return None
        return t

    def capacity(self, n_tokens):
        cf = self.capacity_factor if self.training \
            else self.eval_capacity_factor
        # expert-choice: capacity is tokens-per-expert (Zhou et al.),
        # independent of top_k (which EC routing never uses)
        k = 1 if self.gate == "expert_choice" else self.top_k
        c = int(math.ceil(cf * k * n_tokens / self.num_experts))
        return max(1, min(n_tokens, c))

    def forward(self, x):
        if mesh_mod.degree("ep") > 1 and self.w1.pspec[0] is None:
            raise ValueError(
                "MoELayer was constructed before the expert-parallel mesh "
                "existed (its experts would silently replicate): call "
                "fleet.init / mesh.build_mesh(ep=...) BEFORE building the "
                "model")
        shape = x.shape
        d = shape[-1]
        n = 1
        for s in shape[:-1]:
            n *= s
        x2 = x.reshape([n, d])
        if self.gate == "expert_choice":
            out = engine.apply(
                "moe_ffn_expert_choice", moe_ffn_expert_choice,
                [x2, self.gate_weight, self.w1, self.b1, self.w2,
                 self.b2],
                {"capacity": self.capacity(n), "act": self.activation,
                 "z_loss_weight": self.z_loss_weight})
        else:
            out = engine.apply(
                "moe_ffn", moe_ffn,
                [x2, self.gate_weight, self.w1, self.b1, self.w2,
                 self.b2],
                {"top_k": self.top_k, "capacity": self.capacity(n),
                 "act": self.activation,
                 "z_loss_weight": self.z_loss_weight})
        y, aux = out
        # bypass Layer.__setattr__: the live aux Tensor must NOT register
        # as a parameter (it is a per-forward activation)
        object.__setattr__(self, "_aux_loss", aux)
        return y.reshape(list(shape))


def moe_aux_loss(model):
    """Sum the load-balancing aux losses of every MoELayer after a forward
    (the reference accumulates them on the gate objects the same way).
    Returns a scalar Tensor, or None if the model has no routed layers."""
    total = None
    for layer in model.sublayers(include_self=True):
        if isinstance(layer, MoELayer) and layer.aux_loss is not None:
            total = layer.aux_loss if total is None \
                else total + layer.aux_loss
    return total
