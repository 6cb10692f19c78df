"""Distributed fused train step (reference analog: Fleet's hybrid-parallel
engine — python/paddle/distributed/fleet/meta_parallel/* + sharding
optimizer stages).

One pjit'd XLA program implements the whole hybrid strategy:
  * dp: batch sharded P("dp") on axis 0; XLA emits the grad all-reduce.
  * mp: params annotated by the tensor-parallel layers (param.pspec); GSPMD
    inserts the mp collectives inside fwd/bwd.
  * sharding stage1/2 (ZeRO): optimizer state (and thus the update compute)
    sharded over "dp" on each param's largest divisible axis; XLA emits
    reduce-scatter + all-gather exactly like the reference's sharding stages,
    but derived from annotations.
  * stage3 (FSDP): the params themselves get the "dp" sharding.
Everything is donated, so weights/optimizer state update in place in HBM.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import observability as _obs
from ..autograd import engine as _engine
from ..observability import compile_tracker as _ct
from ..jit import compile_cache as _cc
from ..jit import functional_bridge as FB
from ..framework import random as _random
from ..tensor import Tensor
from . import mesh as mesh_mod
from .pipeline import pipeline_apply_1f1b, pipeline_apply_hybrid


def _largest_divisible_axis(shape, degree, taken=()):
    best, best_ax = 0, None
    for i, s in enumerate(shape):
        if i in taken:
            continue
        if s % degree == 0 and s > best:
            best, best_ax = s, i
    return best_ax


def param_pspec(p, stage=0):
    """PartitionSpec for a parameter: its mp annotation, plus 'dp' sharding of
    the largest free axis when ZeRO stage 3."""
    spec = list(p.pspec) if p.pspec is not None else [None] * p._array.ndim
    while len(spec) < p._array.ndim:
        spec.append(None)
    if stage >= 3:
        taken = tuple(i for i, s in enumerate(spec) if s is not None)
        ax = _largest_divisible_axis(p._array.shape,
                                     mesh_mod.degree("dp"), taken)
        if ax is not None:
            spec[ax] = "dp"
    return P(*spec)


def state_pspec(p_spec, shape, stage):
    """Optimizer-state sharding: like its param, plus 'dp' on the largest free
    axis for stage>=1 (ZeRO-1/2)."""
    spec = list(p_spec)
    while len(spec) < len(shape):
        spec.append(None)
    spec = spec[:len(shape)]
    if stage >= 1 and "dp" not in spec:
        taken = tuple(i for i, s in enumerate(spec) if s is not None)
        ax = _largest_divisible_axis(shape, mesh_mod.degree("dp"), taken)
        if ax is not None and spec[ax] is None:
            spec[ax] = "dp"
    return P(*spec)


class _PipelineShim:
    """Stands in for the model inside the traced loss_fn when pp>1: calling
    it runs pre → GPipe shard_map over the pp axis → post, so unmodified
    loss_fns (e.g. gpt_loss_fn) transparently get a pipelined forward."""

    def __init__(self, model, run_pipeline):
        object.__setattr__(self, "_pt_model", model)
        object.__setattr__(self, "_pt_run", run_pipeline)

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_pt_model"), name)

    def __call__(self, *args, **kwargs):
        return object.__getattribute__(self, "_pt_run")(*args, **kwargs)


class DistributedTrainStep:
    """Fused hybrid-parallel train step over the global mesh."""

    def __init__(self, model, loss_fn, optimizer, strategy=None,
                 batch_axis=0, guard=None):
        from ..resilience import guard as _guard_mod
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._guard = guard if guard is not None \
            else _guard_mod.env_guard()
        self.strategy = strategy
        self.sharding_stage = 0
        hc = {}
        if strategy is not None:
            hc = strategy.hybrid_configs
            self.sharding_stage = int(hc.get("sharding_stage", 0) or 0)
            if hc.get("sharding_degree", 1) and \
                    int(hc.get("sharding_degree", 1)) > 1 and \
                    self.sharding_stage == 0:
                self.sharding_stage = 1
        self.pp = mesh_mod.degree("pp")
        self.use_pp = self.pp > 1
        if self.use_pp and not hasattr(model, "pipeline_decompose"):
            raise ValueError(
                "pp_degree > 1 requires the model to implement "
                "pipeline_decompose() (blocks/pre/post stage plan)")
        # pp x MoE works since round 3: router aux losses ride the
        # pipelined scan as an explicit per-step output (pipeline.py)
        pc = getattr(strategy, "pipeline_configs", None) or {}
        self.n_microbatches = int(
            pc.get("accumulate_steps") if int(pc.get(
                "accumulate_steps", 1) or 1) > 1
            else hc.get("accumulate_steps") or self.pp)
        # interleaved "virtual pipeline" chunks per device (reference:
        # num_virtual_pipeline_stages in fleet pp_layers)
        self.vpp = int(hc.get("virtual_pp_degree")
                       or pc.get("num_virtual_pipeline_stages") or 1)
        # pipeline schedule (reference: schedule_mode in fleet pipeline
        # configs): "1F1B" = hand-written two-scan custom_vjp holding only
        # the per-microbatch boundary activations per device (the default
        # — it beats the 1F1B analytic memory budget, docs/pp_memory.md;
        # vpp>1 composes it with the interleaved wave, Megatron's
        # production schedule); "F-then-B" = differentiable GPipe /
        # interleaved scan.
        sched = (pc.get("schedule_mode") or hc.get("pp_schedule")
                 or "1F1B")
        self.pp_schedule = str(sched).upper().replace("-", "")
        if self.pp_schedule not in ("1F1B", "FTHENB", "GPIPE"):
            raise ValueError(
                f"unknown pipeline schedule_mode {sched!r}: expected "
                "'1F1B' or 'F-then-B'")
        if self.vpp > 1 and self.n_microbatches < self.pp:
            raise ValueError(
                f"virtual_pp_degree>1 needs accumulate_steps "
                f"({self.n_microbatches}) >= pp_degree ({self.pp})")
        self._pp_state = None  # (outer_named, blocks, leaf_names, decomp)
        self._stacked = None   # {leaf_name: [pp, L/pp, ...] array}
        self._model_stale = False
        self._jitted = None
        self._opt_state = None
        self._step = 0
        self._placed = False
        self._fn_cache = None   # persistent compile cache frontend (lazy)
        self._cc_resolved = None  # (batch-shape key, runner) steady state

    # --------------------------------------------------------- pp splitting
    def _pp_split(self):
        """Split params into non-block ("outer") and stacked block leaves."""
        if self._pp_state is not None:
            return self._pp_state
        decomp = self.model.pipeline_decompose()
        blocks = decomp["blocks"]
        if len(blocks) % (self.pp * self.vpp) != 0:
            raise ValueError(
                f"{len(blocks)} pipeline blocks do not divide into "
                f"pp_degree={self.pp} x virtual_pp_degree={self.vpp} "
                "virtual stages")
        # blocks may hold buffers (read-only inside the pipelined scan:
        # rope tables, eval-mode BN stats); mutation raises at trace time
        # in _make_run_pipeline's block_apply
        block_ids = {id(p) for b in blocks for _, p in b.named_parameters()}
        outer_named = [(n, p) for n, p in self.model.named_parameters()
                       if id(p) not in block_ids]
        leaf_names = [n for n, _ in blocks[0].named_parameters()]
        self._pp_state = (outer_named, blocks, leaf_names, decomp)
        return self._pp_state

    def _stacked_specs(self, blocks, leaf_names):
        """PartitionSpec per stacked leaf: P("pp", None, *block_pspec), plus
        a "dp" axis on the largest free dim when ZeRO stage 3."""
        specs = {}
        b0 = dict(blocks[0].named_parameters())
        for ln in leaf_names:
            p = b0[ln]
            base = list(p.pspec) if p.pspec is not None \
                else [None] * p._array.ndim
            while len(base) < p._array.ndim:
                base.append(None)
            spec = ["pp", None] + base
            if self.sharding_stage >= 3:
                shape = (self.pp, len(blocks) // self.pp) + p._array.shape
                taken = tuple(i for i, s in enumerate(spec) if s is not None)
                ax = _largest_divisible_axis(shape, mesh_mod.degree("dp"),
                                             taken)
                if ax is not None:
                    spec[ax] = "dp"
            specs[ln] = P(*spec)
        return specs

    def _block_order(self, n_blocks):
        """Block index for each stacked row, flattened [pp, lps].

        GPipe (vpp==1): identity.  Interleaved: device p's rows hold chunks
        v=0..vpp-1 of lpc layers each, chunk v covering global virtual stage
        v*pp + p — i.e. row (p, j) ← block (j//lpc*pp + p)*lpc + j%lpc."""
        pp, vpp = self.pp, self.vpp
        lps = n_blocks // pp
        if vpp == 1:
            return list(range(n_blocks))
        lpc = lps // vpp
        return [(j // lpc * pp + p) * lpc + j % lpc
                for p in range(pp) for j in range(lps)]

    def _stack_blocks(self, blocks, leaf_names):
        """Stack per-block params into [pp, layers_per_stage, ...] leaves
        (rows permuted per _block_order for the interleaved schedule)."""
        pp = self.pp
        lps = len(blocks) // pp
        mesh = mesh_mod.get_mesh()
        specs = self._stacked_specs(blocks, leaf_names)
        block_params = [dict(b.named_parameters()) for b in blocks]
        order = self._block_order(len(blocks))
        stacked = {}
        for ln in leaf_names:
            arrs = [block_params[i][ln]._array for i in order]
            leaf = jnp.stack(arrs).reshape((pp, lps) + arrs[0].shape)
            stacked[ln] = jax.device_put(
                leaf, NamedSharding(mesh, specs[ln]))
        return stacked, specs

    def sync_model(self):
        """Scatter the stacked block leaves back into the eager model's
        per-block parameters (needed before state_dict/checkpoint save).
        Clears the auto-sync hook afterwards so a later training phase
        (eager, or another engine) can't be clobbered by this engine's
        by-then-stale stacked copy."""
        if getattr(self.model, "_pp_sync", None) == self.sync_model:
            self.model._pp_sync = None
        if not self.use_pp or self._stacked is None or not self._model_stale:
            return
        outer_named, blocks, leaf_names, _ = self._pp_split()
        block_params = [dict(b.named_parameters()) for b in blocks]
        order = self._block_order(len(blocks))
        for ln in leaf_names:
            leaf = self._stacked[ln]
            flat = leaf.reshape((len(blocks),) + leaf.shape[2:])
            for j, i in enumerate(order):
                block_params[i][ln]._inplace_assign(flat[j])
        self._model_stale = False

    # ------------------------------------------------------------ shardings
    def _shardings(self):
        mesh = mesh_mod.get_mesh()
        stage = self.sharding_stage
        if self.use_pp:
            outer_named, _, _, _ = self._pp_split()
            params = [p for _, p in outer_named]
        else:
            params = list(dict(self.model.named_parameters()).values())
        p_specs = [param_pspec(p, stage) for p in params]
        p_sh = [NamedSharding(mesh, s) for s in p_specs]
        b_sh = [NamedSharding(mesh, P())
                for _ in dict(self.model.named_buffers())]
        return params, p_specs, p_sh, b_sh

    def _flat_param_arrays(self):
        """Training-state arrays in optimizer order: outer params, then (pp)
        the stacked block leaves."""
        params, p_specs, _, _ = self._shardings()
        arrays = [p._array for p in params]
        specs = list(p_specs)
        if self.use_pp:
            outer_named, blocks, leaf_names, _ = self._pp_split()
            st_specs = self._stacked_specs(blocks, leaf_names)
            for ln in leaf_names:
                arrays.append(self._stacked[ln])
                specs.append(st_specs[ln])
        return arrays, specs

    def _place_state(self):
        """Device_put params/buffers/opt state with their target shardings
        once, so the jitted step never re-lays-out."""
        # adopt the model: flush any previous pp engine's pending sync so
        # we start from the latest weights, and take over the hook
        prev_sync = getattr(self.model, "_pp_sync", None)
        if prev_sync is not None and prev_sync != self.sync_model:
            prev_sync()
        params, p_specs, p_sh, b_sh = self._shardings()
        from ..resilience import reshard as _reshard_mod
        for p, sh in zip(params, p_sh):
            # reshard-aware placement: a param restored (or trained)
            # under a DIFFERENT mesh redistributes via the planned
            # collective decomposition instead of a blind device_put
            p._inplace_assign(_reshard_mod.place(p._array, sh))
        buffers = list(dict(self.model.named_buffers()).values())
        for b, sh in zip(buffers, b_sh):
            b._inplace_assign(jax.device_put(b._array, sh))
        mesh = mesh_mod.get_mesh()
        if self.use_pp and self._stacked is None:
            outer_named, blocks, leaf_names, _ = self._pp_split()
            self._stacked, _ = self._stack_blocks(blocks, leaf_names)
            # fleet-order bookkeeping (outer params, then stacked leaves) —
            # kept on the engine and passed into optimizer.update() so the
            # optimizer's own parameter lists stay untouched.  A stacked
            # leaf is represented by its block-0 param: full model name
            # (so user apply_decay_param_fun predicates keep working) and
            # param group.
            full_by_id = {id(p): n for n, p in self.model.named_parameters()}
            gmap = getattr(self.optimizer, "_group_by_id", {})
            b0 = dict(blocks[0].named_parameters())
            flat_ps = [p for _, p in outer_named] + \
                [b0[ln] for ln in leaf_names]
            self._fleet_param_names = [full_by_id[id(p)] for p in flat_ps]
            self._fleet_lr_scales = [
                gmap.get(id(p), (1.0, None))[0] for p in flat_ps]
            self._fleet_wd_overrides = [
                gmap.get(id(p), (1.0, None))[1] for p in flat_ps]
            self._fleet_init_frozen = [p.stop_gradient for p in flat_ps]
        if not self.use_pp:
            self._fleet_param_names = [
                n for n, _ in self.model.named_parameters()]
            self._fleet_init_frozen = [
                p.stop_gradient for _, p in self.model.named_parameters()]
        arrays, flat_specs = self._flat_param_arrays()
        if self._opt_state is None:
            # frozen params (stop_gradient — e.g. a LoRA fine-tune's base
            # under the hybrid engine) get NO optimizer slots; the step's
            # None-grad masking passes their empty slots through untouched
            self._opt_state = self.optimizer.init_state(
                arrays, frozen=getattr(self, "_fleet_init_frozen", None))
        self._merge_pending_sd()
        placed_state = []
        for slots, spec in zip(self._opt_state, flat_specs):
            placed = {}
            for name, arr in slots.items():
                sh = NamedSharding(mesh, state_pspec(spec, arr.shape,
                                                     self.sharding_stage))
                placed[name] = jax.device_put(arr, sh)
            placed_state.append(placed)
        self._opt_state = placed_state
        self._placed = True

    # ------------------------------------------------------- checkpointing
    def _topology_tag(self):
        return f"pp{self.pp}xvpp{self.vpp}"

    def _slot_keys(self):
        """Yield (key, slots, slot_name) over fleet-order optimizer state —
        the single source of the checkpoint key scheme."""
        n_outer = len(self._fleet_param_names) if not self.use_pp else \
            len(self._pp_split()[0])
        for i, (name, slots) in enumerate(zip(self._fleet_param_names,
                                              self._opt_state)):
            stacked = self.use_pp and i >= n_outer
            for s in slots:
                key = f"{name}/__stacked__/{s}" if stacked else \
                    f"{name}/{s}"
                yield key, slots, s

    def state_dict(self):
        """Optimizer-format state dict for checkpoint.save_state(optimizer=
        step).  Non-pp entries use the exact eager-optimizer key format
        ("<param>/<slot>"), so fleet checkpoints resume into eager runs and
        vice versa; pp-stacked leaves are saved under
        "<block0 param>/__stacked__/<slot>" (topology-bound: resume needs
        the same pp x virtual_pp split, recorded in __fleet_topology__)."""
        out = {"step": self._step}
        from ..optimizer.lr import LRScheduler
        if isinstance(self.optimizer._lr, LRScheduler):
            out["LR_Scheduler"] = self.optimizer._lr.state_dict()
        if self.use_pp:
            out["__fleet_topology__"] = self._topology_tag()
        if self._opt_state is None:
            # not placed yet: pass through any still-pending loaded state
            # so save-after-load-before-step doesn't drop the moments
            for k, v in (getattr(self, "_pending_sd", None) or {}).items():
                out[k] = Tensor._from_array(v)
            return out
        for key, slots, s in self._slot_keys():
            out[key] = Tensor._from_array(slots[s])
        return out

    def set_state_dict(self, state):
        """Inverse of state_dict(); may be called before or after the first
        step (pending state is merged when the engine places its arrays)."""
        # validate BEFORE mutating anything, so a rejected checkpoint
        # leaves the engine untouched
        pending = {
            k: (v._array if isinstance(v, Tensor) else jnp.asarray(v))
            for k, v in state.items()
            if k not in ("step", "LR_Scheduler", "__fleet_topology__")}
        tag = state.get("__fleet_topology__")
        if tag is not None:
            tag = str(np.asarray(tag)) if not isinstance(tag, str) else tag
        has_stacked = any("/__stacked__/" in k for k in pending)
        if self.use_pp:
            if tag is not None and tag != self._topology_tag():
                raise ValueError(
                    f"fleet checkpoint topology {tag} does not match this "
                    f"engine ({self._topology_tag()}); stacked optimizer "
                    "rows would be assigned to the wrong layers")
            if pending and not has_stacked:
                raise ValueError(
                    "checkpoint has no __stacked__ optimizer entries — it "
                    "was saved by a non-pp run and cannot seed a pp engine")
        elif has_stacked:
            raise ValueError(
                "checkpoint contains pp-stacked optimizer entries; this "
                "engine runs pp=1 — resume with the saving topology "
                f"({tag or 'unknown'})")
        self._step = int(state.get("step", 0))
        self.optimizer._step_count = self._step
        from ..optimizer.lr import LRScheduler
        if "LR_Scheduler" in state and isinstance(self.optimizer._lr,
                                                  LRScheduler):
            self.optimizer._lr.set_state_dict(state["LR_Scheduler"])
        self._pending_sd = pending
        if self._placed:
            self._merge_pending_sd()
            # flush trained block weights to the eager model first (a
            # weights-only or moments-only load must not lose them), then
            # drop the stacked copy so the next call restacks from the
            # now-current eager params and re-places with shardings
            self.sync_model()
            self._stacked = None
            self._placed = False

    def _merge_pending_sd(self):
        sd = getattr(self, "_pending_sd", None)
        if not sd or self._opt_state is None:
            return
        for key, slots, s in self._slot_keys():
            if key in sd:
                slots[s] = sd[key]
        self._pending_sd = None

    def restore_shardings(self):
        """Target shardings for a cross-mesh checkpoint restore, keyed by
        checkpoint tree path: ``model/<param>`` / ``model/<buffer>`` map
        to concrete NamedShardings on the current mesh, and
        ``optimizer/<param>`` prefixes map to ``shape -> NamedSharding``
        callables (slot shapes are only known at restore time).
        CheckpointManager.restore feeds this to resilience.reshard so a
        resized-mesh restart redistributes arrays device-side instead of
        bouncing them through replicated host copies.  pp-stacked block
        leaves are topology-bound and keep the host path (no entry
        here)."""
        if not mesh_mod.has_mesh():
            return {}
        mesh = mesh_mod.get_mesh()
        stage = self.sharding_stage
        targets = {}
        pp_outer = None
        if self.use_pp:
            outer_named, _, _, _ = self._pp_split()
            pp_outer = {n for n, _ in outer_named}

        def _slot_target(p_spec):
            return lambda shape: NamedSharding(
                mesh, state_pspec(p_spec, shape, stage))

        for n, p in self.model.named_parameters():
            if pp_outer is not None and n not in pp_outer:
                continue
            spec = param_pspec(p, stage)
            targets[f"model/{n}"] = NamedSharding(mesh, spec)
            targets[f"optimizer/{n}"] = _slot_target(spec)
        repl = NamedSharding(mesh, P())
        for n, _ in self.model.named_buffers():
            targets[f"model/{n}"] = repl
        return targets

    # ------------------------------------------------------- multi-process
    def _globalize_batch(self, batch_arrays):
        """Multi-controller dp: each launch process feeds its LOCAL batch;
        assemble the global dp-sharded jax.Array from the per-process
        shards (reference analog: DistributedBatchSampler feeding each
        NCCL rank its slice — here the slices become one global array)."""
        if jax.process_count() == 1:
            return batch_arrays
        import numpy as np
        mesh = mesh_mod.get_mesh()
        out = []
        for a in batch_arrays:
            if a.ndim == 0:
                out.append(a)
                continue
            spec = P(*(["dp"] + [None] * (a.ndim - 1)))
            out.append(jax.make_array_from_process_local_data(
                NamedSharding(mesh, spec), np.asarray(a)))
        return tuple(out)

    # ----------------------------------------------------------------- step
    def _make_run_pipeline(self, stacked, rng):
        """Closure the shim calls in place of model.__call__: pre → GPipe
        shard_map over "pp" (dp/mp left to GSPMD inside) → post.

        Block buffers (rope tables, eval-BN stats) are stacked from the
        traced per-model buffer args into [pp, lps, ...] leaves and ride
        the same stacked tree as the params (prefix "buf::"), read-only;
        MoE router aux losses come back as the pipeline's aux output and
        are restored onto the model's first MoE layer so loss fns using
        incubate.moe_aux_loss() keep working under pp."""
        outer_named, blocks, leaf_names, decomp = self._pp_split()
        mesh = mesh_mod.get_mesh()
        template = blocks[0]
        M = self.n_microbatches
        remat = bool(decomp.get("remat", False))

        buf_leaf_names = [n for n, _ in blocks[0].named_buffers()]
        stacked_all = dict(stacked)
        if buf_leaf_names:
            # called inside compute_loss's model-level _swapped: each block
            # buffer's ._array IS the traced per-model buffer argument
            order = self._block_order(len(blocks))
            lps = len(blocks) // self.pp
            per_block = [dict(b.named_buffers()) for b in blocks]
            for ln in buf_leaf_names:
                arrs = [per_block[i][ln]._array for i in order]
                stacked_all["buf::" + ln] = jnp.stack(arrs).reshape(
                    (self.pp, lps) + arrs[0].shape)

        from ..incubate.nn.moe import MoELayer
        moes = [l for b in blocks for l in b.sublayers(include_self=True)
                if isinstance(l, MoELayer)]

        # EVERY schedule (GPipe, 1F1B, interleaved 1F1B, and since round 4
        # the differentiable F-then-B interleaved scan) threads block
        # buffers through the schedule scan, so train-mode BN running
        # stats update per active (chunk, microbatch) step in order
        def block_apply(leaf_dict, h, key):
            arrs = [leaf_dict[n] for n in leaf_names]
            bufs = [leaf_dict["buf::" + n] for n in buf_leaf_names]
            with FB._swapped(template, leaf_names, arrs,
                             buf_leaf_names, bufs) as (_, tbufs):
                # no tape: the schedule differentiates this function with
                # jax.vjp, also from the hand-written backward, which runs
                # outside compute_loss's no_grad — a taped op would be a
                # vjp inside that vjp, and the flash kernel's forward rule
                # has no JVP ("pallas_call ... AssertionError")
                with _random.key_context(key), _engine.no_grad():
                    out = template(Tensor._from_array(h))
                # capture BEFORE _swapped restores arrays
                new_bufs = {"buf::" + n: tbufs[n]._array
                            for n in buf_leaf_names}
            aux = jnp.zeros((), jnp.float32)
            for l in template.sublayers(include_self=True):
                if isinstance(l, MoELayer) and l.aux_loss is not None:
                    aux = aux + l.aux_loss._array.astype(jnp.float32)
                    l.restore_aux_loss(None)  # don't leak tracers
            return out._array, aux, new_bufs

        if remat:
            block_apply = jax.checkpoint(block_apply)

        def run(x, *a, **kw):
            h = decomp["pre"](x, *a, **kw)
            harr = h._array
            B = harr.shape[0]
            if B % M != 0:
                raise ValueError(
                    f"batch {B} not divisible by {M} microbatches "
                    "(strategy.hybrid_configs['accumulate_steps'])")
            mb = B // M
            x_mb = harr.reshape((M, mb) + harr.shape[1:])
            if mesh_mod.degree("dp") > 1:
                x_mb = jax.lax.with_sharding_constraint(
                    x_mb, NamedSharding(mesh, P(None, "dp")))
            mut = bool(buf_leaf_names)
            if self.pp_schedule == "1F1B":
                res = pipeline_apply_1f1b(
                    block_apply, stacked_all, x_mb, rng, mesh,
                    n_stages=self.pp, n_microbatches=M, mutable_bufs=mut,
                    n_chunks=self.vpp)
            else:
                res = pipeline_apply_hybrid(
                    block_apply, stacked_all, x_mb, rng, mesh,
                    n_stages=self.pp, n_microbatches=M, n_chunks=self.vpp,
                    mutable_bufs=mut)
            if mut:
                y_mb, aux_total, new_stacked_bufs = res
                # fold the schedule's committed buffer updates back onto
                # the blocks' (traced) buffer tensors: compute_loss's
                # new_buffers pickup then carries them out of the jit
                order = self._block_order(len(blocks))
                per_block = [dict(b.named_buffers()) for b in blocks]
                for ln in buf_leaf_names:
                    leaf = new_stacked_bufs["buf::" + ln]
                    flat = leaf.reshape((len(blocks),) + leaf.shape[2:])
                    for j, i in enumerate(order):
                        per_block[i][ln]._inplace_assign(flat[j])
            else:
                y_mb, aux_total = res
            y = y_mb.reshape((B,) + y_mb.shape[2:])
            if moes:
                # per-microbatch means averaged over M == full-batch mean
                for l in moes:
                    l.restore_aux_loss(None)
                moes[0].restore_aux_loss(
                    Tensor._from_array(aux_total / float(M)))
            return decomp["post"](Tensor._from_array(y))

        return run

    def _build(self, batch_arrays):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        mesh = mesh_mod.get_mesh()
        use_pp = self.use_pp
        outer_names = None
        bn = [n for n, _ in model.named_buffers()]
        if use_pp:
            outer_named, _, leaf_names, _ = self._pp_split()
            outer_names = [n for n, _ in outer_named]

        def compute_loss(param_tree, buffer_arrays, rng, batch):
            if not use_pp:
                out, new_buffers = FB.call_functional(
                    model, param_tree, buffer_arrays, batch,
                    rng_key=rng, fn=lambda *ts: loss_fn(model, *ts))
                return out, new_buffers
            outer_arrays, stacked = param_tree
            with FB._swapped(model, outer_names, outer_arrays, bn,
                             buffer_arrays) as (_, buffers):
                with _random.key_context(rng), _engine.no_grad():
                    shim = _PipelineShim(
                        model, self._make_run_pipeline(stacked, rng))
                    wrapped = [Tensor._from_array(a) for a in batch]
                    out = loss_fn(shim, *wrapped)
                new_buffers = [buffers[n]._array for n in bn]
            out = out._array if isinstance(out, Tensor) else out
            return out, new_buffers

        def flatten(param_tree):
            if not use_pp:
                return param_tree
            outer_arrays, stacked = param_tree
            return list(outer_arrays) + [stacked[ln] for ln in leaf_names]

        def unflatten(flat, like_tree):
            if not use_pp:
                return flat
            n_outer = len(like_tree[0])
            outer = flat[:n_outer]
            stacked = dict(zip(leaf_names, flat[n_outer:]))
            return (outer, stacked)

        from ..framework import debugging as _dbg
        check = _dbg.enabled()

        gmap = getattr(optimizer, "_group_by_id", {})
        if use_pp:
            fleet_names = self._fleet_param_names
            fleet_scales = self._fleet_lr_scales
            fleet_wds = self._fleet_wd_overrides
            outer_named2, blocks2, leaf_names2, _ = self._pp_split()
            b02 = dict(blocks2[0].named_parameters())
            flat_ps = [p for _, p in outer_named2] + \
                [b02[ln] for ln in leaf_names2]
        else:
            # key ordering was fixed in _place_state (single source for
            # the checkpoint key scheme) — only derive the group scales
            fleet_names = self._fleet_param_names
            flat_ps = [p for _, p in model.named_parameters()]
            fleet_scales = [gmap.get(id(p), (1.0, None))[0]
                            for p in flat_ps]
            fleet_wds = [gmap.get(id(p), (1.0, None))[1] for p in flat_ps]
        # frozen params keep their values; need_clip=False skips clipping
        fleet_frozen = [p.stop_gradient for p in flat_ps]
        fleet_clip = [not fz and (getattr(p, "optimize_attr", None)
                                  or {}).get("need_clip", True)
                      for fz, p in zip(fleet_frozen, flat_ps)]

        from ..resilience import guard as _guard_mod
        guarded = self._guard is not None
        guard_fused = guarded and self._guard.mode == "fused"

        def step_fn(param_tree, buffer_arrays, opt_state, lr, step, rng,
                    batch):
            (loss, new_buffers), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(
                    param_tree, buffer_arrays, rng, batch)
            flat_g = flatten(grads)
            flat_p = flatten(param_tree)
            flat_g = [None if fz else g
                      for g, fz in zip(flat_g, fleet_frozen)]
            finite = _dbg.finite_flags(loss, flat_g) if check else None

            ok = _guard_mod.all_finite(loss, flat_g) if guarded else None
            if guarded and guard_fused:
                # zero grads + lr: bit-exact param no-op that keeps the
                # donated update in-place; the reduction is replicated,
                # so every shard takes the same gate
                flat_g = _guard_mod.gate_grads(ok, flat_g)
                lr = _guard_mod.gate_lr(ok, lr)
            if optimizer._grad_clip is not None:
                flat_g = optimizer._clip_grad_arrays(flat_g,
                                                     need_clip=fleet_clip)
            new_flat, new_opt = optimizer.update(
                flat_g, flat_p, opt_state, lr, step,
                param_names=fleet_names, lr_scales=fleet_scales,
                wd_overrides=fleet_wds)
            new_params = unflatten(new_flat, param_tree)
            if guarded and not guard_fused:
                # exact mode: freeze params + optimizer slots (select)
                new_params, new_opt = _guard_mod.select_tree(
                    ok, (new_params, new_opt), (param_tree, opt_state))
            if guarded:
                new_buffers = _guard_mod.select_tree(ok, new_buffers,
                                                     buffer_arrays)
            return loss, new_params, new_buffers, new_opt, finite, ok

        params, p_specs, p_sh, b_sh = self._shardings()
        arrays, flat_specs = self._flat_param_arrays()
        state_sh = [
            {name: NamedSharding(mesh, state_pspec(spec, arr.shape,
                                                   self.sharding_stage))
             for name, arr in slots.items()}
            for slots, spec in zip(self._opt_state, flat_specs)]
        repl = NamedSharding(mesh, P())
        if use_pp:
            _, blocks, leaf_names_, _ = self._pp_split()
            st_specs = self._stacked_specs(blocks, leaf_names_)
            st_sh = {ln: NamedSharding(mesh, st_specs[ln])
                     for ln in leaf_names_}
            param_in_sh = (p_sh, st_sh)
        else:
            param_in_sh = p_sh
        batch_sh = tuple(
            NamedSharding(mesh, P(*(["dp"] + [None] * (a.ndim - 1))))
            if a.ndim > 0 else repl for a in batch_arrays)
        in_sh = (param_in_sh, b_sh, state_sh, repl, repl, repl, batch_sh)
        out_sh = (repl, param_in_sh, b_sh, state_sh,
                  repl if check else None, repl if guarded else None)
        # constants step_fn bakes in beyond the code: optimizer
        # hyperparameters, model cfg, guard mode, strategy dicts, the
        # debug-check flag — all must key the persistent cache (see the
        # TrainStep analog in jit/train_step.py)
        self._bake_key = _cc.config_fingerprint(
            self.optimizer, getattr(self.model, "cfg", None),
            self._guard, self.strategy) + repr(
            (check, guarded, self.sharding_stage))
        self._cc_resolved = None

        self._jitted = jax.jit(step_fn, in_shardings=in_sh,
                               out_shardings=out_sh,
                               donate_argnums=(0, 2))
        # donation-free twin for the persistent compile cache (same
        # shardings, no aliasing — see compile_cache module docstring)
        self._plain_jit = lambda: jax.jit(step_fn, in_shardings=in_sh,
                                          out_shardings=out_sh)

    def lower(self, *batch):
        """`jax.stages.Lowered` of the fused step for `batch` — the
        program `__call__` compiles, WITHOUT running it: for reading
        its compiled text (kernels, collectives) and memory analysis.
        Consumes no step count and no randomness."""
        model, optimizer = self.model, self.optimizer
        if not self._placed:
            self._place_state()
        batch_arrays = tuple(
            b._array if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch)
        if self._jitted is None:
            self._build(batch_arrays)
        if self.use_pp:
            outer_named, _, leaf_names, _ = self._pp_split()
            param_tree = ([p._array for _, p in outer_named], self._stacked)
        else:
            _, pa, _, _ = FB.split_state(model)
            param_tree = pa
        batch_arrays = self._globalize_batch(batch_arrays)
        ba = [b._array for _, b in model.named_buffers()]
        lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(self._step + 1, jnp.float32)
        return self._jitted.lower(
            param_tree, ba, self._opt_state, lr, step, _random.peek_key(),
            batch_arrays)

    def memory_stats(self, *batch):
        """XLA's CompiledMemoryStats (argument/output/temp bytes) of the
        fused step for `batch` — the peak-memory evidence for pipeline
        schedule choices (tools/pp_memory.py; reference analog: 1F1B's
        activation-memory motivation in fleet pipeline_parallel.py)."""
        return self.lower(*batch).compile().memory_analysis()

    def __call__(self, *batch):
        model, optimizer = self.model, self.optimizer
        if not self._placed:
            self._place_state()
        batch_arrays = tuple(
            b._array if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch)
        from ..resilience import chaos as _chaos
        # chaos site: the whole fleet is killed for an elastic restart —
        # the harness restarts on a different world size and the retained
        # checkpoint reshards onto the new mesh (chaos_check --mesh-change)
        _chaos.crash("restart.mesh_change")
        if self._jitted is None:
            # chaos site: a compile failure must surface once and succeed
            # on retry (_jitted stays None, the next call rebuilds)
            _chaos.crash("compile.fail_once")
            self._build(batch_arrays)
        if self.use_pp:
            outer_named, _, leaf_names, _ = self._pp_split()
            pn = [n for n, _ in outer_named]
            pa = [p._array for _, p in outer_named]
            param_tree = (pa, self._stacked)
        else:
            pn, pa, _, _ = FB.split_state(model)
            param_tree = pa
        if _chaos._PLAN is not None and _chaos.fire("step.nonfinite"):
            batch_arrays = _chaos.poison_batch(batch_arrays)
        batch_arrays = self._globalize_batch(batch_arrays)
        bn = [n for n, _ in model.named_buffers()]
        ba = [b._array for _, b in model.named_buffers()]
        self._step += 1
        lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(self._step, jnp.float32)
        rng = _random.next_key()
        tok = t0 = None
        if _obs.enabled():
            tok = _ct.on_call(
                f"DistributedTrainStep({type(model).__name__})",
                _ct.signature_of(
                    jax.tree_util.tree_leaves(param_tree) + list(ba) +
                    list(batch_arrays)),
                owner=self)
            t0 = time.perf_counter()
        args = (param_tree, ba, self._opt_state, lr, step, rng,
                batch_arrays)
        runner, outcome = self._jitted, None
        if _cc.enabled():
            # persistent compile cache (the mesh fingerprint is part of
            # the key: a resized elastic mesh can never replay a stale
            # executable from the previous world size).  Steady state
            # (same batch shapes) skips the full digest — see TrainStep
            bkey = tuple((tuple(a.shape), str(a.dtype))
                         for a in batch_arrays)
            if (self._cc_resolved is not None
                    and self._cc_resolved[0] == bkey):
                runner = self._cc_resolved[1]
            else:
                if self._fn_cache is None:
                    self._fn_cache = _cc.FunctionCache(
                        f"DistributedTrainStep({type(model).__name__})",
                        fingerprint=(type(model), self.loss_fn,
                                     type(self.optimizer)))
                runner, outcome, _ = self._fn_cache.lookup(
                    self._jitted, args, static=(self._bake_key,),
                    plain_jit=self._plain_jit)
                self._cc_resolved = (bkey, runner)
        try:
            loss, new_params, new_buffers, self._opt_state, finite, ok = \
                runner(*args)
        except BaseException:
            if tok is not None:
                _ct.abort(tok)
            raise
        if tok is not None:
            # "mem" (memo reuse) did not compile either — see TrainStep
            _ct.finish(tok, cache_hit=(outcome in ("hit", "mem")))
        if t0 is not None:
            _obs.trace.add_complete("fleet_step", "step", t0,
                                    time.perf_counter() - t0,
                                    args={"step": self._step})
        if finite is not None:
            from ..framework import debugging as _dbg
            _dbg.raise_on_nonfinite(
                finite, getattr(self, "_fleet_param_names", None)
                or self.optimizer._param_names, self._step)
        params = dict(model.named_parameters())
        if self.use_pp:
            new_outer, self._stacked = new_params
            for n, a in zip(pn, new_outer):
                params[n]._inplace_assign(a)
            self._model_stale = True
            # state_dict() auto-syncs the stacked stage params back
            model._pp_sync = self.sync_model
        else:
            for n, a in zip(pn, new_params):
                params[n]._inplace_assign(a)
        buffers = dict(model.named_buffers())
        for n, a in zip(bn, new_buffers):
            buffers[n]._inplace_assign(a)
        if ok is not None:
            # after the assignments: a guard rollback restores checkpoint
            # state through set_state_dict and must not be overwritten
            self._guard.after_step(ok, self)
        optimizer._step_count = self._step
        return Tensor._from_array(loss)
