"""LLMEngine — continuous (in-flight) batching over the paged KV pool.

The engine owns exactly TWO program shapes, so steady-state serving
never recompiles:

* **one decode program** over the whole pool: [max_running] static
  request slots, each consuming one token through its block table
  (dead slots ride along with write-limit 0);
* **one prefill program per shape bucket** (PR 7's ladder —
  `generation.BucketPolicy`): a prompt chunk padded up a bucket streams
  its K/V into the pool; the lm_head matmul is dead code XLA prunes,
  so prefill pays attention+MLP only.

`step()` is one scheduler iteration: admit → bounded prefill chunking →
dispatch one batched decode program → stream/finish the picks of the
program dispatched a step AGO.  The engine runs one decode program ahead
of the host: a greedy row's next token is the previous program's pick,
taken on the device (`prev_ids[src]`) before the host has seen it, so the
device never stands still while the host emits a step and builds the
next.  A row that draws its token on the host (`do_sample`) cannot
chain; a step that holds one lands what is in flight first and then runs
dispatch → wait → emit in place.  Long prompts chunk across many steps
while every decode-ready request still advances one token per step —
prefill never stalls in-flight decode.

Token parity: with greedy sampling the engine's per-request output is
token-identical to a sequential `generation.generate` call — decode
attends gathered pool blocks with the exact `sdpa` math (see
`paged_attention` in ops/nn_kernels.py), and tests/test_serving.py
asserts the equality under concurrent interleaved requests.

Per-request latency telemetry (TTFT/TPOT/queue-wait percentiles, pool
and queue gauges) flows into the PR-2 metrics registry; see
docs/serving.md for the full table.
"""
from __future__ import annotations

import collections
import functools
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from ..autograd import engine as _autograd
from ..distributed import mesh as mesh_mod
from ..jit import functional_bridge as FB
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..observability.compile_tracker import building as _building
from ..ops.pallas import (pool_blocks_read, pool_positions_read,
                          pool_walk_copies)
from ..resilience import chaos
from ..tensor import Tensor
from ..text.generation import BucketPolicy
from .block_pool import BlockPool, PoolExhausted, band_blocks
from .scheduler import RUNNING, Request, Scheduler, clock


class ShedRequest(RuntimeError):
    """Admission-control refusal — the structured "fast no" overload
    degrades to instead of unbounded queueing.  `reason` names the
    watermark that tripped (``queue_depth`` / ``free_blocks`` /
    ``draining`` / ``no_healthy_replica``); `detail` carries the gauge
    values at refusal time so callers (and clients) can see why."""

    def __init__(self, reason, **detail):
        self.reason = reason
        self.detail = detail
        extras = ", ".join(f"{k}={v}" for k, v in detail.items())
        super().__init__(f"request shed ({reason}"
                         + (f": {extras}" if extras else "") + ")")


class LLMEngine:
    def __init__(self, model, num_blocks=64, block_size=16, max_running=8,
                 prefill_chunk=64, buckets=None, max_model_len=None,
                 dtype=None, shed_queue_depth=None, shed_free_blocks=None,
                 promote_after=4):
        """`num_blocks` sizes the pool's first block group (the layers
        that keep the whole context, where the model has any).  A window
        group beside it gets what `max_running` requests hold at most,
        each its band and one chunk: the scheduler admits against that
        count, so such a group cannot run dry."""
        windows = {getattr(layer, "window", None)
                   for layer in model.cache_planes()} - {None}
        if getattr(getattr(model, "cfg", None), "sliding_window", None) \
                and not windows:
            raise NotImplementedError(
                "this model has a sliding_window and its cache_planes() "
                "name no layer's window: the pool would keep, and the "
                "paged ops read, the full context")
        self.model = model
        model.eval()
        self.pool = BlockPool.for_model(
            model, num_blocks, block_size=block_size, dtype=dtype,
            slots=max_running,
            window_blocks={w: int(max_running) * band_blocks(
                w, prefill_chunk, block_size) for w in windows})
        self._window_groups = [g for g, grp in enumerate(self.pool.groups)
                               if grp.window is not None]
        sharded = self.pool.shard_()
        self.scheduler = Scheduler(self.pool, max_running=max_running,
                                   promote_after=promote_after,
                                   run_tokens=prefill_chunk)
        self.max_running = int(max_running)
        # admission-control watermarks (None = never shed): overload
        # must degrade to fast structured refusals, not unbounded p99
        self.shed_queue_depth = (None if shed_queue_depth is None
                                 else int(shed_queue_depth))
        self.shed_free_blocks = (None if shed_free_blocks is None
                                 else int(shed_free_blocks))
        self._draining = False
        self._closed = False
        self.prefill_chunk = int(prefill_chunk)
        self.policy = buckets if isinstance(buckets, BucketPolicy) \
            else BucketPolicy(buckets=buckets)
        max_pos = getattr(model.cfg, "max_position_embeddings", None)
        self.max_model_len = int(max_model_len or max_pos
                                 or num_blocks * block_size)
        if max_pos is not None:
            self.max_model_len = min(self.max_model_len, int(max_pos))
        self.table_cols = self.pool.blocks_for(self.max_model_len)
        # pool blocks a layer of the decode program reads: by the op the
        # model says reads its planes, one query token a slot (for
        # step()'s counts)
        gate = dict(table_cols=self.table_cols, rows=self.max_running,
                    heads=model.cfg.num_heads,
                    dtype=next(iter(model.parameters()))._array.dtype)
        self._blocks_read = [functools.partial(
            pool_blocks_read, model.cache_op,
            plane_shapes=self.pool.plane_shapes(g), window=grp.window,
            **gate) for g, grp in enumerate(self.pool.groups)]
        # ... and the positions it scores and reads, where the op picks
        # what it reads (nothing for the others)
        self._positions_read = functools.partial(
            pool_positions_read, model.cache_op,
            plane_shapes=self.pool.plane_shapes(0), **gate,
            **getattr(model, "cache_op_args", {}))
        # ... and the DMAs its walk issues, where it copies runs of
        # adjacent blocks (nothing for the others)
        self._walk_copies = functools.partial(
            pool_walk_copies, model.cache_op,
            plane_shapes=self.pool.plane_shapes(0), **gate,
            **getattr(model, "cache_op_args", {}))

        self._pn, self._p_arrays, self._bn, self._b_arrays = \
            FB.split_state(model)
        self._programs = {}     # key -> live jitted program
        # routed layers' load of chunks, unread yet, each with its tokens
        self._chunk_loads = []
        # experts a token is sent to, where the model routes: with it the
        # step counts the assignments ROUTED beside those its (held)
        # experts received
        self._top_k = getattr(model.cfg, "num_experts_per_tok", None)
        # the decode program dispatched and not landed yet (its picks
        # are emitted by the next step), and the newest program's `ids`
        # ON THE DEVICE, from which the next one takes its chained rows'
        # tokens; zeros stand for it before the first step, so that
        # every call has one argument form and the program compiles once
        self._flight = None
        self._prev_ids = jnp.zeros(self.max_running, jnp.int32)
        if sharded:     # as the program under the mesh returns its ids
            self._prev_ids = jax.device_put(self._prev_ids,
                                            mesh_mod.replicated())
        self._aot_execs = {}    # key -> deserialized AOT executable
        self._finished = []
        self._reg = _metrics.registry()

    # ------------------------------------------------------------- requests
    def add_request(self, prompt_ids, max_new_tokens=20, eos_token_id=None,
                    do_sample=False, temperature=1.0, top_k=None,
                    top_p=None, seed=0, on_token=None, on_finish=None,
                    resume_tokens=None, arrival_t=None,
                    queue_deadline_s=None, ttl_s=None, shed_exempt=False):
        """Queue a request; returns the Request handle (its `generated`
        list fills in as `step()` runs; `on_token(req, tok)` streams).

        `resume_tokens` seeds already-generated tokens (router failover:
        the survivor re-prefills prompt+resume and continues decoding at
        the next position — the preemption-resume path, so continuation
        is token-identical).  `arrival_t` preserves the original arrival
        across a failover so `ttl_s` keeps meaning total lifetime: seconds
        on `scheduler.clock()` (Unix time, the span recorder's clock), NOT
        `time.monotonic()`, against which a TTL would expire at once.
        `shed_exempt` bypasses the admission watermarks: a failed-over
        request already held capacity once — shedding it would tear a
        live stream to save queue slots it is owed.

        Raises :class:`ShedRequest` when an admission watermark trips
        (a structured refusal — nothing was allocated), ValueError /
        PoolExhausted on requests that could never be served."""
        if self._closed:
            raise RuntimeError("engine is closed")
        prompt = np.asarray(prompt_ids).reshape(-1).astype(np.int64)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_model_len:
            raise ValueError(
                f"request needs {total} positions but the replica serves "
                f"max_model_len={self.max_model_len}")
        for g, grp in enumerate(self.pool.groups):
            need = self.scheduler.most_blocks(g, total)
            if need > grp.num_blocks:
                raise PoolExhausted(
                    f"request needs {need} {grp.name} blocks; pool has "
                    f"{grp.num_blocks} total")
        if resume_tokens and len(resume_tokens) >= int(max_new_tokens):
            raise ValueError(
                f"resume_tokens already holds {len(resume_tokens)} of "
                f"max_new_tokens={max_new_tokens} — nothing left to "
                f"generate")
        if not shed_exempt:
            self._check_shed()
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id, do_sample=do_sample,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      seed=seed, on_token=on_token, on_finish=on_finish,
                      resume_tokens=resume_tokens, arrival_t=arrival_t,
                      queue_deadline_s=queue_deadline_s, ttl_s=ttl_s)
        if chaos.fire("serving.request_poison", tag=req.id):
            req.poisoned = True
        self.scheduler.submit(req)
        self._reg.counter("serving_requests_submitted_total").inc()
        return req

    def _check_shed(self):
        """Admission control: refuse-with-reason BEFORE any allocation
        when a watermark is crossed, so overload costs the client one
        exception instead of an unbounded queue wait."""
        sched = self.scheduler
        if self._draining:
            self._shed("draining", queue_depth=sched.queue_depth)
        if (self.shed_queue_depth is not None
                and sched.queue_depth >= self.shed_queue_depth):
            self._shed("queue_depth", queue_depth=sched.queue_depth,
                       watermark=self.shed_queue_depth)
        # low free blocks only sheds when a backlog already exists —
        # with an empty queue the request admits immediately and normal
        # preemption handles transient pool pressure
        if (self.shed_free_blocks is not None and sched.queue_depth > 0
                and self.pool.free_blocks < self.shed_free_blocks):
            self._shed("free_blocks", free_blocks=self.pool.free_blocks,
                       watermark=self.shed_free_blocks,
                       queue_depth=sched.queue_depth)

    def _shed(self, reason, **detail):
        self._reg.counter("serving_requests_shed_total",
                          reason=reason).inc()
        raise ShedRequest(reason, **detail)

    @property
    def has_work(self):
        """True until every token is delivered: a request leaves
        `running` when its last token is EMITTED, and picks in flight
        (of finished requests too: they are dropped) are work."""
        return bool(self.scheduler.waiting or self.scheduler.running
                    or self._flight is not None)

    def metrics_snapshot(self, prefix="serving_"):
        """Point-in-time snapshot of this replica's serving metrics —
        the registry records whose name starts with `prefix` (a str or
        a tuple of strs).  JSON-serializable by construction: this is
        the payload of the process-per-replica ``metrics_snapshot``
        RPC, and what `tools/serve.py --proc` merges into its final
        report (each worker process owns its own registry)."""
        if isinstance(prefix, str):
            prefix = (prefix,)
        return [rec for rec in self._reg.snapshot()
                if rec["name"].startswith(tuple(prefix))]

    def run(self, max_steps=None):
        """Drive step() until the queues drain (or max_steps)."""
        n = 0
        while self.has_work and (max_steps is None or n < max_steps):
            self.step()
            n += 1
        return n

    def generate_batch(self, prompts, max_new_tokens=20, **kw):
        """Convenience: submit every prompt, drain, return the generated
        token lists in submission order."""
        reqs = [self.add_request(p, max_new_tokens=max_new_tokens, **kw)
                for p in prompts]
        self.run()
        return [list(r.generated) for r in reqs]

    # ----------------------------------------------------------------- step
    def step(self):
        """One continuous-batching iteration.  Returns a summary dict:
        `decoded` rows DISPATCHED in this step's decode program,
        `emitted` tokens streamed by this step (the picks of the program
        a step ago, or this step's own where a `do_sample` row made it
        synchronous), `admitted`, `prefilled`, `running`, `waiting`.

        Every step writes its phases to the span recorder (see
        docs/serving.md): `serving.step` is the root, its children name
        what the host does while the device waits or works."""
        sched = self.scheduler
        with _trace.traced("serving.step", cat="serving") as root:
            with _trace.traced("serving.schedule", parent=root.sid,
                               cat="serving"):
                now = clock()
                self._expire(now)
                admitted = sched.admit()
                for req in admitted:
                    self._reg.counter(
                        "serving_requests_admitted_total").inc()
                    self._reg.histogram(
                        "serving_queue_wait_seconds").observe(
                            now - req.arrival_t)
                    if req.admitted_t is None:
                        req.admitted_t = now
                        if not req.needs_prefill:   # a one-token prompt
                            req.prefill_done_t = now

            # ---- prefill lane: a bounded token budget per step
            budget = self.prefill_chunk
            prefilled = freed = 0
            for req in list(sched.running):
                if budget <= 0:
                    break
                if not req.needs_prefill:
                    continue
                n = min(budget, req.feed_len - 1 - req.ctx)
                # a window group hands out the chunk's blocks now; a
                # group that is dry keeps the chunk for a later step
                if req.state != RUNNING or \
                        not sched.reserve(req, req.ctx + n):
                    continue
                self._prefill(req, n, root.sid)
                freed += sched.trim(req)
                if req.prefill_done_t is None and not req.needs_prefill:
                    req.prefill_done_t = clock()
                budget -= n
                prefilled += n

            # ---- decode lane: every decode-ready request advances one
            # token.  The order follows the rows: a row that draws its
            # token on the host needs the token before its next row can
            # be built, so a step that holds one lands what is in flight
            # first and its own program in place; every other step
            # dispatches first and lands the program of the step before
            # while its own runs
            landed = collections.Counter(
                rows_picked_on_device=0, logit_rows_fetched=0,
                rows_dropped=0, emitted=0)
            behind = self._flight
            in_place = any(r.do_sample and r.decode_ready
                           for r in sched.running)
            if in_place:
                self._land(behind, root.sid, landed)
                behind = None
            with _trace.traced("serving.schedule", parent=root.sid,
                               cat="serving"):
                ready = []
                for req in [r for r in sched.running if r.decode_ready]:
                    if req.state != RUNNING:
                        continue        # a victim of an earlier grow()
                    if sched.grow(req):
                        ready.append(req)
                ready = [r for r in ready if r.state == RUNNING]
            # ready ⊆ running and admit() caps running at max_running, so
            # the static decode program always has a slot for every row
            assert len(ready) <= self.max_running
            chained = 0
            blocks = self._block_counts(ready)
            if ready:
                chained = sum(1 for r in ready if r.in_flight)
                self._flight = self._dispatch(ready, root.sid, root.counts)
                freed += sum(sched.trim(r) for r in ready)
            self._land(behind, root.sid, landed)
            if in_place:
                self._land(self._flight, root.sid, landed)

            self._reg.gauge("serving_queue_depth").set(sched.queue_depth)
            self._reg.gauge("serving_running_requests").set(
                len(sched.running))
            self._reg.gauge("serving_free_blocks").set(
                self.pool.free_blocks)
            emitted = landed.pop("emitted")
            root.counts.update(decode_rows=len(ready), rows_chained=chained,
                               **blocks, **landed)
            if self._window_groups:
                root.counts.update(window_blocks_freed=freed)
                self._reg.gauge("serving_window_blocks_in_use").set(sum(
                    self.pool.used_in(g) for g in self._window_groups))
            if self.pool.slots:
                # the decode rows' recurrent state, read and written once
                # by this step's program
                in_use = self.pool.slots - self.pool.free_slots
                self._reg.gauge("serving_state_slots_in_use").set(in_use)
                root.counts.update(
                    state_slots_live=len(ready),
                    state_bytes_rw=2 * self.pool.state_bytes() * len(ready))
        return {"admitted": len(admitted), "decoded": len(ready),
                "emitted": emitted, "prefilled": prefilled,
                "running": len(sched.running),
                "waiting": sched.queue_depth}

    def _block_counts(self, ready):
        """How far the decode program's attention follows the traffic,
        for the step's root: the blocks the rows live in, and the blocks
        a layer reads for all slots (a dead slot shows the length 1) on
        the path that serves the program: the kernel's ragged walk, or
        the fallback's gather of whole tables.  A window group counts
        under its own names: the blocks its rows HOLD, what one full
        table would hold more, and the blocks that hold a position a
        row's query sees (the least any sound walk reads).  An op that
        picks what it reads adds the positions it scores and reads
        (`pool_positions_read`)."""
        counts = collections.Counter(kv_blocks_live=0, kv_blocks_walked=0)
        lens = [r.ctx + 1 for r in ready]
        counts.update(self._positions_read(lens))
        if not ready:
            return counts
        pool = self.pool
        dead = [1] * (self.max_running - len(ready))
        whole = sum(pool.blocks_for(n) for n in lens)
        for g, grp in enumerate(pool.groups):
            walked = self._blocks_read[g](lens + dead)
            if grp.window is None:
                counts["kv_blocks_live"] += whole
                counts["kv_blocks_walked"] += walked
                continue
            held = sum(len(r.block_tables[g]) - r.behind[g] for r in ready)
            counts["window_blocks_live"] += held
            counts["window_blocks_walked"] += walked
            counts["window_blocks_saved"] += whole - held
            counts["window_blocks_band"] += whole - sum(
                max(n - grp.window, 0) // pool.block_size for n in lens)
        return counts

    def _expire(self, now):
        """Deadline sweep: queue-wait and TTL expiry are CLEAN finishes
        — blocks freed, `on_finish` fired with a structured reason —
        never a stuck slot."""
        sched = self.scheduler
        for req in list(sched.waiting) + list(sched.running):
            why = req.expiry(now)
            if why is not None:
                self._finish(req, f"expired-{why}")

    # ------------------------------------------------------ drain / close
    def cancel(self, req, reason="cancelled"):
        """Abort a queued or running request: frees its blocks, fires
        `on_finish` with the given reason; a pick of it still in flight
        is dropped when it lands.  No-op once finished."""
        if req.finish_reason is None:
            self._finish(req, reason)

    def drain(self, ttl_s=None, max_steps=None):
        """Graceful shutdown, phase 1 (the CheckpointManager preemption-
        flush pattern: the signal handler only records, the main loop
        flushes): stop admitting (`add_request` sheds with reason
        ``draining``), expire every queued request immediately, then
        step until running work finishes — or, past ``ttl_s`` seconds,
        expire what remains.  Returns a summary dict."""
        self._draining = True
        already = sum(1 for r in self._finished
                      if r.finish_reason == "drained")
        for req in list(self.scheduler.waiting):
            self._finish(req, "drained")
        deadline = None if ttl_s is None else clock() + ttl_s
        n = 0
        while (self.scheduler.running or self._flight is not None) and \
                (max_steps is None or n < max_steps):
            if deadline is not None and clock() > deadline:
                for req in list(self.scheduler.running):
                    self._finish(req, "drained")
                self._flight = None     # every pick in it is surplus now
                break
            self.step()
            n += 1
        return {"steps": n,
                "drained": sum(1 for r in self._finished
                               if r.finish_reason == "drained")
                - already}

    def close(self):
        """Graceful shutdown, phase 2: expire any work still live, then
        release the pool's device arrays and compiled programs.  Returns
        `pool.check_leaks()` (must be clean — the drill asserts it)."""
        for req in (list(self.scheduler.running)
                    + list(self.scheduler.waiting)):
            self._finish(req, "drained")
        leaks = self.pool.check_leaks()
        self.pool.release()
        self._flight = self._prev_ids = None    # picks in flight: dropped
        self._chunk_loads = []
        self._programs.clear()
        self._aot_execs.clear()
        self._closed = True
        self._draining = True
        return leaks

    # ------------------------------------------------------------- programs
    def retire_aot(self, key=None):
        """Drop loaded AOT executables (all, or one key) so the next call
        compiles the donating live program.  AOT artifacts are serialized
        ALIAS-FREE (serving.aot), so on donating backends a warm-started
        replica copies the pool every step until the bridge is retired —
        call this at a quiet moment once the replica is warm.  Returns
        the retired keys."""
        keys = [key] if key is not None else list(self._aot_execs)
        for k in keys:
            self._aot_execs.pop(k, None)
        return keys

    def _run_program(self, key, builder, *args, parent=None):
        """Call the program of `key`, built by `builder()` on its first
        call.  That build and first call run under a `serving.compile`
        span (child of `parent`, count `program`) that splits them into
        JAX's trace, lowering and compile (`compile_tracker.building`)."""
        fn = self._aot_execs.get(key)
        if fn is not None:
            try:
                return fn(*args)
            except TypeError as e:
                warnings.warn(
                    f"serving AOT executable {key} rejected this call "
                    f"({e}); falling back to live jit", UserWarning,
                    stacklevel=2)
                del self._aot_execs[key]
        jit_fn = self._programs.get(key)
        if jit_fn is not None:
            return jit_fn(*args)
        with _building("serving.compile", parent=parent,
                       program=":".join(map(str, key))):
            jit_fn = self._programs[key] = builder()
            return jit_fn(*args)

    @staticmethod
    def _donate_pools():
        """Donate the pool buffers through the live decode/prefill
        programs (they are pure pool -> pool updates, and the engine
        drops its old references right after the call) — without
        donation every step copies the whole pool per layer.  CPU can't
        alias donated buffers (jax warns and copies anyway), and AOT
        export must stay alias-free (deserialized alias-baked
        executables are the PR-7 segfault class) — both get the
        non-donating build."""
        return jax.default_backend() != "cpu"

    def _caches(self, planes, tables, pos, limit, more=()):
        """One cache dict per layer over the planes that layer has, as
        the models' paged branches read them: the rows' block tables (a
        layer gets its group's) and, where the pool holds per-request
        planes, their slots.  `more`: the slots, where there are any,
        then the tables of the groups after the first."""
        more = list(more)
        shared = dict(pos=Tensor._from_array(pos),
                      limit=Tensor._from_array(limit))
        if self.pool.slots:
            shared["slot"] = Tensor._from_array(more.pop(0))
        tables = [Tensor._from_array(a) for a in [tables] + more]
        return [dict({name: Tensor._from_array(arrays[i])
                      for name, arrays in planes.items()
                      if arrays[i] is not None},
                     table=tables[self.pool.group_of[i]], **shared)
                for i in range(self.pool.num_layers)]

    @staticmethod
    def _written(caches, planes, layers=None):
        """What a program hands back of its caches: ({name: the planes as
        written},) and, where routed layers left their load (the real
        tokens each expert received), the stack [routed layers, experts]
        of the first `layers` layers' behind it."""
        out = ({name: [c[name]._array if name in c else None
                       for c in caches] for name in planes},)
        load = [c["expert_load"]._array for c in caches[:layers]
                if "expert_load" in c]
        return out + ((jnp.stack(load),) if load else ())

    def _build_decode(self, donate=None):
        model, pn, bn = self.model, self._pn, self._bn

        def pure(p_arrays, b_arrays, planes, tables, pos, tokens, limit,
                 prev_ids, src, *more):
            # a chained row's token is the pick of row `src` of the
            # decode program before this one, read where it lies: the
            # host has not seen it yet.  src < 0: the host's `tokens`
            tokens = jnp.where(src >= 0, prev_ids[jnp.maximum(src, 0)],
                               tokens)
            caches = self._caches(planes, tables, pos, limit, more)
            with FB._swapped(model, pn, p_arrays, bn, b_arrays):
                with _autograd.no_grad():
                    logits = model(Tensor._from_array(tokens[:, None]),
                                   caches=caches)
            # the greedy choice and the finite test are made here, over
            # the float32 row the host would scan (first index on a tie,
            # as np.argmax), so that a step brings [max_running] ids to
            # the host and the logits stay on the device
            rows = logits._array[:, -1, :].astype(jnp.float32)
            return (rows, jnp.argmax(rows, -1).astype(jnp.int32),
                    jnp.isfinite(rows).all(-1)
                    ) + self._written(caches, planes)

        donate = self._donate_pools() if donate is None else donate
        return jax.jit(pure, donate_argnums=(2,) if donate else ())

    def _build_prefill(self, donate=None):
        model, pn, bn = self.model, self._pn, self._bn

        def pure(p_arrays, b_arrays, planes, table, pos, tokens, limit,
                 *more):
            caches = self._caches(planes, table, pos, limit, more)
            with FB._swapped(model, pn, p_arrays, bn, b_arrays):
                with _autograd.no_grad():
                    model(Tensor._from_array(tokens), caches=caches)
            # only the written pools (and the routed layers' load) leave
            # the program: the lm_head matmul (and every logit) is dead
            # code XLA prunes, and with it all of the LAST layer but the
            # rows it caches.  That layer's load is left out, so that its
            # attention and routing stay dead: a chunk's counts are those
            # of the products that run.  (A last layer that carries a
            # recurrent state keeps its token mixer: the state it writes
            # is returned; its expert layer stays dead)
            return self._written(caches, planes, layers=-1)

        donate = self._donate_pools() if donate is None else donate
        return jax.jit(pure, donate_argnums=(2,) if donate else ())

    def program_keys(self, prompt_lens=()):
        """The program inventory a replica needs: the decode program
        plus one prefill program per ladder bucket up to the chunk
        bucket.  The WHOLE sub-ladder is included — the prefill lane
        splits one per-step token budget across concurrently-admitted
        requests, so live chunk sizes (and therefore buckets) below
        `prefill_chunk` all occur regardless of prompt lengths;
        `prompt_lens` is kept for callers that want to assert coverage
        of specific workloads (chunks never exceed the budget, so it
        can only add buckets already in the ladder)."""
        cap = self.policy.bucket(self.prefill_chunk)
        buckets, n = set(), 1
        while True:
            b = self.policy.bucket(n)
            buckets.add(b)
            if b >= cap:
                break
            n = b + 1
        for n in prompt_lens:
            buckets.add(self.policy.bucket(
                min(max(int(n) - 1, 1), self.prefill_chunk)))
        return [("decode",)] + sorted(("prefill", b) for b in buckets)

    def program_structs(self, key):
        """(builder, example ShapeDtypeStructs) for AOT lowering.  The
        builder produces the ALIAS-FREE (non-donating) build — serialized
        alias-baked executables are the PR-7 segfault class."""
        s = jax.ShapeDtypeStruct
        p = [s(a.shape, a.dtype) for a in self._p_arrays]
        b = [s(a.shape, a.dtype) for a in self._b_arrays]
        planes = {name: [a if a is None else s(a.shape, a.dtype)
                         for a in arrays]
                  for name, arrays in self.pool.planes.items()}
        i32 = np.int32
        # last ride the rows' slots, where the pool has per-request
        # planes, and the tables of the block groups after the first
        more = lambda rows: ((s((rows,), i32),) if self.pool.slots else ()) \
            + (s((rows, self.table_cols), i32),) * (len(self.pool.groups) - 1)
        if key[0] == "decode":
            R, M = self.max_running, self.table_cols
            return functools.partial(self._build_decode, donate=False), (
                p, b, planes, s((R, M), i32), s((R,), i32), s((R,), i32),
                s((R,), i32), s((R,), i32), s((R,), i32)) + more(R)
        if key[0] == "prefill":
            Lb = int(key[1])
            return functools.partial(self._build_prefill, donate=False), (
                p, b, planes, s((1, self.table_cols), i32), s((1,), i32),
                s((1, Lb), i32), s((1,), i32)) + more(1)
        raise KeyError(f"unknown serving program key {key!r}")

    # ------------------------------------------------------------- prefill
    def _prefill(self, req, n, parent=None):
        bucket = self.policy.bucket(n)
        with _trace.traced("serving.prefill", parent=parent, rid=req.id,
                           cat="serving") as span:
            span.counts.update(tokens=n, ctx=req.ctx,
                               **self._chunk_block_counts(req.ctx, n, bucket))
            feed = req.feed_tokens()
            chunk = feed[req.ctx:req.ctx + n]
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = chunk
            tables = self._tables([req])
            pos = np.asarray([req.ctx], np.int32)
            limit = np.asarray([req.ctx + n], np.int32)
            slots = () if req.state_slot is None \
                else (np.asarray([req.state_slot], np.int32),)
            span.counts.update(self._walk_copies(tables[0], pos, rows=1,
                                                 queries=bucket))
            self.pool.planes, *load = self._run_program(
                ("prefill", bucket), self._build_prefill,
                self._p_arrays, self._b_arrays, self.pool.planes,
                tables[0], pos, tokens, limit, *slots, *tables[1:],
                parent=span.sid)
            for a in load:
                # read when a decode's fetch has next waited for the
                # device (step()): a chunk never waits for its own
                a.copy_to_host_async()
                self._chunk_loads.append((a, n))
        req.ctx += n
        self._reg.counter("serving_prefill_tokens_total").inc(n)

    def _chunk_block_counts(self, ctx, n, bucket):
        """How far a prefill program's attention follows its chunk, for
        the chunk's span, summed over the pool's layer kinds: the blocks
        that hold a position one of the chunk's `n` queries sees, and
        the blocks a layer reads for the program's `bucket` query rows
        on the path that serves it (a kernel's walk, or the fallback's
        gather of a whole table or band); and where the op picks what it
        reads, the (query, position) pairs it scores, picks and attends."""
        bs = self.pool.block_size
        live = walked = 0
        for g, grp in enumerate(self.pool.groups):
            behind = 0 if grp.window is None \
                else max(ctx - (grp.window - 1), 0) // bs
            live += self.pool.blocks_for(ctx + n) - behind
            walked += self._blocks_read[g]([ctx + bucket], rows=1,
                                           queries=bucket)
        return dict(kv_blocks_live=live, kv_blocks_walked=walked,
                    **self._positions_read([ctx + n], rows=1,
                                           queries=bucket, real=n))

    def _tables(self, reqs, rows=None):
        """The requests' block tables as a program takes them: one
        [rows, table_cols] array a block group, a request a row."""
        out = [np.zeros((rows or len(reqs), self.table_cols), np.int32)
               for _ in self.pool.groups]
        for i, req in enumerate(reqs):
            for table, ids in zip(out, req.block_tables):
                table[i, :len(ids)] = ids
        return out

    # -------------------------------------------------------------- decode
    def _dispatch(self, ready, parent=None, counts=None):
        """Build and dispatch one decode program over `ready`, and queue
        the copies of what the host reads of it behind it.  Returns the
        flight; nothing waits here.  A row with a pick in flight takes
        its token from that pick on the device (`src` names its row in
        the program before); the host sends the token of every other
        row.  `counts` (the step root's) takes the DMAs of a walk that
        copies runs of adjacent blocks, counted from the tables sent."""
        R = self.max_running
        with _trace.traced("serving.decode.prepare", parent=parent,
                           cat="serving"):
            tables = self._tables(ready, R)
            pos = np.zeros(R, np.int32)
            tokens = np.zeros(R, np.int32)
            limit = np.zeros(R, np.int32)   # 0 = dead slot, writes dropped
            src = np.full(R, -1, np.int32)  # -1 = the host's token
            for i, req in enumerate(ready):
                pos[i] = req.ctx
                limit[i] = req.ctx + 1
                if req.in_flight:
                    src[i] = req.slot
                else:
                    tokens[i] = req.feed_tokens()[req.ctx]
            slots = ()
            if self.pool.slots:
                # every row a slot of its own: the live rows theirs, the
                # dead rows those no live row holds (which they leave as
                # they are), so that the step can update the pool in place
                taken = [r.state_slot for r in ready]
                spare = sorted(set(range(self.pool.slots)) - set(taken))
                slots = (np.asarray(taken + spare[:R - len(taken)],
                                    np.int32),)
            if counts is not None:
                counts.update(self._walk_copies(tables[0], pos))
        # the logits are copied only for a row that draws its token on
        # the host; a greedy step leaves them on the device
        sampled = any(r.do_sample for r in ready)
        with _trace.traced("serving.decode.dispatch", parent=parent,
                           cat="serving") as span:
            logits, ids, finite, self.pool.planes, *load = self._run_program(
                ("decode",), self._build_decode,
                self._p_arrays, self._b_arrays, self.pool.planes,
                tables[0], pos, tokens, limit, self._prev_ids, src, *slots,
                *tables[1:], parent=span.sid)
            self._prev_ids = ids
            # the copies are queued behind the program at once: left to
            # start after a wait has returned they cost the step 0.2 ms
            # (PERF.md, PR 25)
            for a in [ids, finite] + load + ([logits] if sampled else []):
                a.copy_to_host_async()
        # the chunks dispatched before this program have ended when its
        # ids arrive: their loads are read with it
        flight = _Flight(list(ready), logits, ids, finite, load,
                         self._chunk_loads, sampled)
        self._chunk_loads = []
        for i, req in enumerate(ready):
            req.ctx += 1
            req.in_flight += 1
            req.slot = i
        self._reg.counter("serving_decode_steps_total").inc()
        self._reg.histogram("serving_decode_batch").observe(len(ready))
        return flight

    def _land(self, flight, parent, landed):
        """Wait for a dispatched decode program, fetch its picks and
        emit them; the counts of what it brought are added to `landed`.
        A row whose request has finished meanwhile (EOS, a failed finite
        test, a cancel or an expiry are seen one step late) is a surplus
        row: its pick is dropped, never emitted, and counted
        (`rows_dropped`).  No-op on None."""
        if flight is None:
            return
        if flight is self._flight:
            self._flight = None
        # the wait is its own span, so that the fetch times the copy
        # alone
        with _trace.traced("serving.decode.wait", parent=parent,
                           cat="serving"):
            flight.ids.block_until_ready()
        with _trace.traced("serving.decode.fetch", parent=parent,
                           cat="serving"):
            flight.fetch()
        with _trace.traced("serving.sample", parent=parent,
                           cat="serving"):
            now = clock()
            for i, req in enumerate(flight.rows):
                if req is not None:
                    req.in_flight -= 1
                if req is None or req.finish_reason is not None:
                    landed["rows_dropped"] += 1
                    continue
                self._emit(req, _Row(flight, i), now)
                landed["emitted"] += 1
                if (req.in_flight and req.finish_reason is None
                        and req.generated[-1] != flight.ids[i]):
                    # the host chose another token than the program's
                    # pick (a wrapper of `_emit` handed on a row of its
                    # own): the row already chained on that pick is
                    # surplus, and the host feeds the position again
                    self._flight.rows[req.slot] = None
                    req.in_flight -= 1
                    # a recurrent state cannot step back: it is built
                    # again from the first position
                    if req.state_slot is not None:
                        self.scheduler.rewind(req)
                    else:
                        req.ctx -= 1
        if flight.host is not None:
            landed["logit_rows_fetched"] += self.max_running
        else:
            landed["rows_picked_on_device"] += flight.picked
        if flight.load is not None:
            # [routed layers, experts] live rows each received; and the
            # load of the chunks that ran before the program
            landed["moe_assignments"] += int(flight.load.sum())
            landed["experts_touched"] += int((flight.load > 0).sum())
            landed["prefill_moe_assignments"] += sum(
                int(a.sum()) for a, _ in flight.chunks)
            landed["prefill_experts_touched"] += sum(
                int((a > 0).sum()) for a, _ in flight.chunks)
            if self._top_k:
                landed["moe_assignments_routed"] += len(flight.rows) \
                    * self._top_k * len(flight.load)
                landed["prefill_moe_assignments_routed"] += sum(
                    n * self._top_k * len(a) for a, n in flight.chunks)

    def _emit(self, req, logits_row, now):
        """The hook a decoded row goes through: `logits_row` is the
        step's `_Row` (the device's token and finite test, the float32
        logits behind `np.asarray`), or an ndarray a wrapper hands on,
        which is tested and sampled on the host."""
        if req.poisoned:
            # chaos serving.request_poison: this request's logits are
            # ruined; the guard below must fail IT without touching the
            # rest of the batch
            finite = False
        elif isinstance(logits_row, _Row):
            finite = logits_row.finite
        else:
            finite = np.isfinite(logits_row).all()
        if not finite:
            self._finish(req, "error")
            return
        tok = _sample_row(req, logits_row)
        req.generated.append(tok)
        if req.first_token_t is None:
            req.first_token_t = now
            if not req.resumed:
                # a failed-over request's replica-local TTFT is not an
                # arrival→first-token latency; the router's routed
                # histograms own the end-to-end number
                self._reg.histogram("serving_ttft_seconds").observe(
                    now - req.arrival_t)
        elif req.last_token_t is not None:
            self._reg.histogram("serving_tpot_seconds").observe(
                now - req.last_token_t)
        req.last_token_t = now
        self._reg.counter("serving_tokens_generated_total").inc()
        if req.on_token is not None:
            req.on_token(req, tok)
            if req.finish_reason is not None:
                return    # the callback cancelled/finished the request
        if req.eos_token_id is not None and tok == req.eos_token_id:
            self._finish(req, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(req, "length")

    def _finish(self, req, reason):
        if req.finish_reason is not None:
            return        # already settled: finishing is idempotent
        self.scheduler.finish(req, reason)
        self._finished.append(req)
        self._record_request(req)
        if reason in ("eos", "length"):
            self._reg.counter("serving_requests_finished_total").inc()
        elif reason in ("error", "cancelled"):
            self._reg.counter("serving_requests_failed_total").inc()
        elif reason == "drained":
            self._reg.counter("serving_requests_expired_total",
                              where="drain").inc()
        elif reason.startswith("expired-"):
            self._reg.counter("serving_requests_expired_total",
                              where=reason[len("expired-"):]).inc()
        else:
            self._reg.counter("serving_requests_failed_total").inc()
        if req.on_finish is not None:
            req.on_finish(req)

    @staticmethod
    def _record_request(req):
        """The request's life as ONE `serving.request` span, arrival to
        finish, with the marks in between (ns on the recorder's clock; a
        mark it never reached is left out) as its counts."""
        marks = {"admitted": req.admitted_t,
                 "prefill_done": req.prefill_done_t,
                 "first_token": req.first_token_t}
        _trace.record(
            "serving.request", int(req.arrival_t * 1e9), _trace.now_ns(),
            rid=req.id, cat="serving",
            counts={k: int(t * 1e9) for k, t in marks.items()
                    if t is not None})


class _Flight:
    """One decode program from its dispatch to the step that lands it:
    the requests by slot, the program's choice and finite test of every
    row (device arrays until `fetch`), the routed layers' load with that
    of the chunks dispatched before it, and the float32 logits, on the
    device until somebody needs a row of them; one copy then serves the
    whole program."""

    def __init__(self, rows, logits, ids, finite, load, chunks, sampled):
        self.rows, self.logits, self.ids, self.finite = (
            rows, logits, ids, finite)
        self.load, self.chunks, self.sampled = load, chunks, sampled
        self.host = None    # the logits, once they have been copied
        self.picked = 0     # rows that took the program's token

    def fetch(self):
        """Bring to the host what the copies queued at dispatch carry."""
        self.ids = np.asarray(self.ids).tolist()
        self.finite = np.asarray(self.finite).tolist()
        if self.sampled:
            self.logits_rows()
        self.load = np.asarray(self.load[0]) if self.load else None
        self.chunks = [(np.asarray(a), n) for a, n in self.chunks]

    def logits_rows(self):
        if self.host is None:
            self.host = np.asarray(self.logits)
        return self.host


class _Row:
    """One row of a landed decode program as `_emit` receives it.  `np.array(row)`
    and `np.asarray(row)` give its float32 logits, fetched at that
    moment."""
    __slots__ = ("_out", "_i")

    def __init__(self, out, i):
        self._out, self._i = out, i

    @property
    def finite(self):
        return self._out.finite[self._i]

    def pick(self):
        """The token the program chose: the row's argmax."""
        self._out.picked += 1
        return self._out.ids[self._i]

    def __array__(self, dtype=None, copy=None):
        row = self._out.logits_rows()[self._i]
        return row if dtype is None else row.astype(dtype)


def _sample_row(req, logits_row):
    """One row's token.  Greedy is the row's argmax — the decode
    program's own where the row carries it, np.argmax over an ndarray:
    either way token-identical to the sequential generate() path.
    Sampled mode stays on the host: it filters the fp32 row through the
    ONE `generation.filter_logits` implementation (so
    temperature/top-k/top-p semantics can never drift from generate())
    and draws from a numpy Generator seeded per (request seed, POSITION)
    — deterministic regardless of batch composition AND of where the
    request is served: a failover resume re-derives exactly the stream a
    single replica would have drawn (one shared stateful Generator could
    not survive a resume — its cursor would restart)."""
    if not req.do_sample:
        if isinstance(logits_row, _Row):
            return logits_row.pick()
        return int(np.argmax(logits_row))
    from ..text.generation import filter_logits
    filtered = filter_logits(jnp.asarray(np.asarray(logits_row))[None, :],
                             req.temperature, req.top_k, req.top_p)[0]
    p = np.asarray(jax.nn.softmax(filtered), dtype=np.float64)
    p = p / p.sum()      # exact renormalization for rng.choice
    rng = np.random.default_rng([req.seed, len(req.generated)])
    return int(rng.choice(len(p), p=p))
