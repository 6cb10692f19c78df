"""Fully-fused train step: forward + backward + optimizer in ONE XLA program.

This is the TPU-performance path the reference reaches via dy2static + CINN +
fused optimizer kernels; here it's a single jax.jit with donated params/opt
state (so weights update in-place in HBM) and value_and_grad for the backward.
The Fleet distributed engine reuses this with sharding annotations
(distributed/fleet_engine.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..framework import random as _random
from ..observability import compile_tracker as _ct
from ..observability import trace as _trace
from ..resilience import chaos as _chaos
from ..resilience import guard as _guard
from ..tensor import Tensor
from . import compile_cache as _cc
from . import functional_bridge as FB


class TrainStep:
    """step = TrainStep(model, loss_fn, optimizer)
       loss = step(*batch)   # batch of Tensors

    loss_fn(model, *batch) -> scalar loss Tensor, evaluated under trace.

    `guard` (a resilience.NonfiniteGuard, or the PADDLE_TPU_GUARD=1
    default) arms the nonfinite-step guard: the fused program skips the
    optimizer update on NaN/inf grads and the guard rolls back to the
    last checkpoint after N consecutive bad steps.  Disabled ⇒ one
    `is None` check per call.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True, guard=None):
        import os
        if os.environ.get("PADDLE_TPU_TRACELINT"):
            from .. import analysis as _analysis
            if _analysis.env_enabled():
                _analysis.check_traceable(type(model).forward)
                _analysis.check_traceable(loss_fn)
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._jitted = None
        self._donate = donate
        self._opt_state = None
        self._step = 0
        self._guard = guard if guard is not None else _guard.env_guard()
        self._fn_cache = None   # persistent compile cache frontend (lazy)
        self._cc_resolved = None  # (batch-shape key, runner) steady state

    def _build(self):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        from ..framework import debugging as _dbg
        check = self._check_numerics = _dbg.enabled()

        def compute_loss(param_arrays, buffer_arrays, rng, batch_arrays):
            out, new_buffers = FB.call_functional(
                model, param_arrays, buffer_arrays, batch_arrays,
                rng_key=rng, fn=lambda *ts: loss_fn(model, *ts))
            loss = out
            return loss, new_buffers

        # engine-order bookkeeping: params flow through in named_parameters
        # order, which may differ from the optimizer's param-group order —
        # align names/group lr scales by identity
        named = list(model.named_parameters())
        gmap = getattr(optimizer, "_group_by_id", {})
        p_names = [n for n, _ in named]
        p_scales = [gmap.get(id(p), (1.0, None))[0] for _, p in named]
        p_wds = [gmap.get(id(p), (1.0, None))[1] for _, p in named]
        # frozen (stop_gradient / ParamAttr(trainable=False)) params stay
        # registered in named_parameters but must not be updated
        p_frozen = [p.stop_gradient for _, p in named]
        p_clip = [not fz and (getattr(p, "optimize_attr", None)
                              or {}).get("need_clip", True)
                  for fz, (_, p) in zip(p_frozen, named)]

        guarded = self._guard is not None
        guard_fused = guarded and self._guard.mode == "fused"

        def step_fn(param_arrays, buffer_arrays, opt_state, lr, step, rng,
                    batch_arrays):
            (loss, new_buffers), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(
                    param_arrays, buffer_arrays, rng, batch_arrays)
            grads = [None if fz else g for g, fz in zip(grads, p_frozen)]
            finite = _dbg.finite_flags(loss, grads) if check else None

            ok = _guard.all_finite(loss, grads) if guarded else None
            if guarded and guard_fused:
                # nonfinite step, fused mode: zero grads + lr so the
                # update is a bit-exact param no-op that still runs
                # in-place under donation (see guard.NonfiniteGuard)
                grads = _guard.gate_grads(ok, grads)
                lr = _guard.gate_lr(ok, lr)
            if optimizer._grad_clip is not None:
                grads = optimizer._clip_grad_arrays(grads,
                                                    need_clip=p_clip)
            new_params, new_opt_state = optimizer.update(
                grads, param_arrays, opt_state, lr, step,
                param_names=p_names, lr_scales=p_scales, wd_overrides=p_wds)
            if guarded and not guard_fused:
                # exact mode: freeze params AND optimizer slots via a
                # select (forfeits in-place reuse of the donated state)
                new_params, new_opt_state = _guard.select_tree(
                    ok, (new_params, new_opt_state),
                    (param_arrays, opt_state))
            if guarded:
                # buffers (running stats) are poisoned by the forward
                # itself; they are small and not donated — select always
                new_buffers = _guard.select_tree(ok, new_buffers,
                                                 buffer_arrays)
            return loss, new_params, new_buffers, new_opt_state, finite, ok

        # everything step_fn bakes in as a CONSTANT beyond the code
        # itself must be part of the persistent-cache key: optimizer
        # hyperparameters, model-config values, guard mode, the
        # debug-check flag, per-param group scales/decay/frozen masks —
        # two runs sharing a cache dir with different momentum (or one
        # guarded, one not) must never share an executable
        self._bake_key = _cc.config_fingerprint(
            optimizer, getattr(model, "cfg", None), self._guard) + repr(
            (check, tuple(p_scales), tuple(p_wds), tuple(p_frozen),
             tuple(p_clip)))
        self._cc_resolved = None

        donate = (0, 2) if self._donate else ()
        self._jitted = jax.jit(step_fn, donate_argnums=donate)
        # donation-free twin for the persistent compile cache: what gets
        # serialized must carry no buffer aliasing (deserialized donated
        # executables segfault — see compile_cache module docstring)
        self._plain_jit = ((lambda: jax.jit(step_fn)) if donate else None)

    def lower(self, *batch):
        """`jax.stages.Lowered` of the fused step for `batch` — the
        program `__call__` compiles, WITHOUT running it: for reading
        its compiled text (is the flash kernel in it?) and memory
        analysis.  Consumes no step count and no randomness."""
        _, pa, _, ba = self._state()
        batch_arrays = tuple(
            b._array if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch)
        return self._jitted.lower(
            pa, ba, self._opt_state,
            jnp.asarray(self.optimizer.get_lr(), jnp.float32),
            jnp.asarray(self._step + 1, jnp.float32),
            _random.peek_key(), batch_arrays)

    def _state(self):
        """(param names, arrays, buffer names, arrays) of the model,
        with the optimizer state adopted and the step built."""
        model, optimizer = self.model, self.optimizer
        sync = getattr(model, "_pp_sync", None)
        if sync is not None:  # flush a prior pp engine's stacked weights
            sync()            # before training eagerly from the model
        pn, pa, bn, ba = FB.split_state(model)
        if self._opt_state is None:
            # adopt any state the optimizer already has; else init —
            # frozen params (stop_gradient) get NO slots (empty dicts):
            # a LoRA/linear-probe fine-tune must not pay optimizer HBM
            # for the frozen base
            frozen = [p.stop_gradient for _, p in model.named_parameters()]
            self._opt_state = optimizer._state or optimizer.init_state(
                pa, frozen=frozen)
            optimizer._state = None  # fused step owns the state now
        if self._jitted is None:
            # chaos site: a compile failure must surface once and succeed
            # on retry (self._jitted stays None, so the next call rebuilds)
            _chaos.crash("compile.fail_once")
            self._build()
        return pn, pa, bn, ba

    def __call__(self, *batch):
        """One fused step.  The host's share of it is the span
        `train.call`; its children `train.call.lookup` (state and
        executable resolved) and `train.call.dispatch` (the runner call,
        which returns before the device finishes) split it."""
        with _trace.traced("train.call", cat="step") as root:
            return self._call(batch, root.sid)

    def _call(self, batch, parent):
        t_lookup = _trace.now_ns()
        model, optimizer = self.model, self.optimizer
        pn, pa, bn, ba = self._state()
        self._step += 1
        lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(self._step, jnp.float32)
        rng = _random.next_key()
        batch_arrays = tuple(
            b._array if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch)
        if _chaos._PLAN is not None and _chaos.fire("step.nonfinite"):
            batch_arrays = _chaos.poison_batch(batch_arrays)
        tok = None
        if _obs.enabled():
            tok = _ct.on_call(
                f"TrainStep({type(model).__name__})",
                _ct.signature_of(list(pa) + list(ba) + list(batch_arrays)),
                owner=self)
        args = (pa, ba, self._opt_state, lr, step, rng, batch_arrays)
        runner, outcome = self._jitted, None
        if _cc.enabled():
            # persistent compile cache: a warm restart loads the
            # serialized executable instead of paying trace+compile.
            # Steady state (same batch shapes as last call — params/
            # opt-state shapes are fixed per instance) skips the full
            # digest: hashing the whole arg tree per step is measurable
            # on sub-ms steps
            bkey = tuple((tuple(a.shape), str(a.dtype))
                         for a in batch_arrays)
            if (self._cc_resolved is not None
                    and self._cc_resolved[0] == bkey):
                runner = self._cc_resolved[1]
            else:
                if self._fn_cache is None:
                    self._fn_cache = _cc.FunctionCache(
                        f"TrainStep({type(model).__name__})",
                        fingerprint=(type(model), self.loss_fn,
                                     type(self.optimizer)))
                runner, outcome, _ = self._fn_cache.lookup(
                    self._jitted, args, static=(self._bake_key,),
                    plain_jit=self._plain_jit)
                self._cc_resolved = (bkey, runner)
        t_dispatch = _trace.now_ns()
        _trace.record("train.call.lookup", t_lookup, t_dispatch,
                      parent=parent, cat="step")
        try:
            loss, new_params, new_buffers, self._opt_state, finite, ok = \
                runner(*args)
        except BaseException:
            if tok is not None:
                _ct.abort(tok)
            raise
        _trace.record("train.call.dispatch", t_dispatch, _trace.now_ns(),
                      parent=parent, cat="step")
        if tok is not None:
            # "mem" (process-global memo reuse) did not compile either —
            # reporting it as a compile would corrupt jit_compiles_total
            _ct.finish(tok, cache_hit=(outcome in ("hit", "mem")))
        if finite is not None:
            from ..framework import debugging as _dbg
            _dbg.raise_on_nonfinite(finite, pn, self._step)
        params = dict(model.named_parameters())
        for n, a in zip(pn, new_params):
            params[n]._inplace_assign(a)
        buffers = dict(model.named_buffers())
        for n, a in zip(bn, new_buffers):
            buffers[n]._inplace_assign(a)
        if ok is not None:
            # AFTER the assignments: a rollback restores checkpoint
            # params into the model, which must not be overwritten by
            # this step's (skipped) outputs
            self._guard.after_step(ok, self)
        optimizer._step_count = self._step
        from ..optimizer.lr import LRScheduler
        if isinstance(optimizer._lr, LRScheduler):
            pass  # user steps the scheduler; lr is re-read every call
        return Tensor._from_array(loss)

    def state_dict(self):
        return {"opt_state": self._opt_state, "step": self._step}

    # --------------------------------------------------------- resilience
    def sync_optimizer_state(self):
        """Hand the fused-step-owned optimizer state back to the eager
        optimizer so state_dict()/save_state sees the live slots (the
        fused step keeps ownership; the handed-back reference is only
        guaranteed fresh until the next __call__)."""
        if self._opt_state is not None:
            self.optimizer._state = self._opt_state
            self.optimizer._step_count = self._step

    def reload_from(self, step=None):
        """After an external checkpoint restore into (model, optimizer):
        re-adopt the optimizer's state on the next call and resync the
        step counter."""
        self._opt_state = None
        if step is not None:
            self._step = int(step)


def train_step(model, loss_fn, optimizer, donate=True, guard=None):
    return TrainStep(model, loss_fn, optimizer, donate=donate, guard=guard)
