#!/usr/bin/env python
"""chaos_check — run the seeded chaos plan end-to-end on a tiny model.

The tier-1 resilience drill (wired in like ``tools/tracelint.py --self``):
one deterministic :class:`ChaosPlan` exercises all four fault families —

  1. loader kill        a shm_loader worker dies on its 2nd batch and is
                        respawned; every batch still arrives, in order
  2. nonfinite step     three consecutive poisoned batches trip the
                        guard: two skips, then rollback to the last
                        retained checkpoint
  3. torn checkpoint    a save crashes after the array commit; the
                        manager resolves latest() past the torn dir
  4. mid-save SIGTERM   preemption lands during save_state; the handler
                        flushes, flags, and a fresh train step resumes
                        IN THE SAME PROCESS

and the recovered run must land on **exactly** the weights/losses of an
uninterrupted reference run over the same batch schedule.  Any drift —
a dropped batch, a half-applied optimizer step, a stale Momentum slot —
fails the drill.

``--mesh-change`` runs the **elastic restart drill** instead: train on a
4-device dp mesh (ZeRO stage 3, params genuinely sharded) with retained
checkpoints, kill the fleet via the ``restart.mesh_change`` chaos site,
restart on a 2-device mesh and restore through the device-side reshard
path (resilience.reshard, arXiv:2112.01075 — asserted via the
``path=device`` counters, no replicated host bounce), then finish the
run.  Along the resumed run an injected ``collective.timeout`` must be
retried by the collective policy without supervisor intervention.  The
post-restore loss trajectory must match the uninterrupted 4-device
reference within ``MESH_TOL`` (dp=4 vs dp=2 only changes the reduction
grouping of the same global batch).

``--cold-start`` runs the **compile-cache drill** instead: train with a
persistent compile cache (jit/compile_cache.py), kill, restart with the
warm cache — the restarted run must perform ZERO compilations (every
jit entry loads its serialized executable) with bit-exact loss
continuity vs an uninterrupted reference; then a deterministically
corrupted cache entry must be quarantined and silently recompiled.

``--serving`` runs the **serving overload drill** instead: 8 requests
against a block pool too small to hold them, with injected pool
exhaustion (``serving.pool_exhausted``) and one poisoned request
(``serving.request_poison``).  The continuous-batching engine must
preempt/resume under pressure with every surviving request's output
token-identical to a sequential ``generate()`` reference, fail only the
poisoned request, and return every block (zero leaks, whole free list).

``--router`` runs the **serving-tier survival drill** instead: a
2-replica router where ``serving.replica_kill`` kills one replica
mid-stream three times (failover re-prefill on the survivor with
overlap-dedup consistency checks, backoff respawns, then crash-loop
abandon), an overload burst must shed with structured reasons, and
``serving.replica_hang`` must be detected via stale heartbeat and
evicted within the configured timeout — with every surviving request's
final token stream byte-identical to the uninterrupted sequential
reference and zero leaked blocks on the survivors.

``--router --proc`` runs the **process-per-replica survival drill**: the
same router state machine, but each replica is a REAL worker process
(`paddle_tpu/serving/worker.py`) behind the framed socket transport.
A worker is ``kill -9``'d mid-stream three times (failover re-prefill on
the survivor, backoff respawns AOT-warm-started from exported serving
artifacts, then crash-loop abandon — every death attributed by waitpid
signal), an injected ``serving.transport_drop`` tears a frame in transit
(must be rejected structurally and evicted, never a silent token gap),
and after ``close()`` every spawned worker pid must be dead AND reaped —
zero orphans, with all surviving streams byte-identical to the
sequential reference and zero leaked blocks on survivors.

Usage:  python tools/chaos_check.py [-v] [--mesh-change] [--cold-start]
        [--serving] [--router [--proc]]
Exit 0 = all recovery paths green.
"""
import argparse
import io
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_STEPS = 10        # total optimizer steps in the drill
BATCHES = 8         # dataset of 16 samples / batch 2, two loader workers
SPEC = ("loader.worker_kill@2#0;"     # family 1: kill worker 0, batch 2
        "step.nonfinite@4*3;"         # family 2: poison step calls 4-6
        "ckpt.crash_after_arrays@2;"  # family 3: tear the 2nd save
        "save.sigterm@3")             # family 4: SIGTERM inside save 3
SEED = 0


class _DrillDataset:
    def __len__(self):
        return 16

    def __getitem__(self, i):
        import numpy as np
        x = np.linspace(0.1 * i, 0.1 * i + 1, 4, dtype=np.float32)
        y = np.asarray([0.3 * i], dtype=np.float32)
        return x, y


def _fresh_step(guard=None):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    paddle.seed(1234)   # identical init for reference / chaos / resumed
    model = nn.Linear(4, 1)
    o = opt.Momentum(learning_rate=0.05, momentum=0.9,
                     parameters=model.parameters())

    def loss_fn(m, x, y):
        return ((m(x) - y) ** 2).mean()

    return model, TrainStep(model, loss_fn, o, guard=guard)


def _drive(ts, batches, upto, losses=None):
    """Advance the train step to `upto` optimizer steps, feeding
    ``batches[_step % len]`` — self-correcting across a guard rollback
    (which rewinds ``_step``)."""
    while ts._step < upto:
        i = ts._step % len(batches)
        loss = ts(*batches[i])
        if losses is not None:
            losses[ts._step] = float(loss.numpy())
    return ts


def run(out=None, verbose=False):
    out = out if out is not None else sys.stdout
    import tempfile
    import shutil
    import warnings

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.chaos import ChaosInterrupt
    from paddle_tpu.resilience.guard import NonfiniteGuard
    from paddle_tpu.resilience.manager import CheckpointManager

    def log(msg):
        if verbose:
            print(msg, file=out)

    root = tempfile.mkdtemp(prefix="chaos_check_")
    failures = []
    try:
        # ---- reference: batch schedule + uninterrupted training --------
        ref_batches = [tuple(b if isinstance(b, (list, tuple)) else [b])
                       for b in DataLoader(_DrillDataset(), batch_size=2,
                                           num_workers=0)]
        assert len(ref_batches) == BATCHES
        _, ref_ts = _fresh_step()
        ref_losses = {}
        _drive(ref_ts, ref_batches, N_STEPS, ref_losses)
        ref_w = np.asarray(ref_ts.model.weight.numpy()).copy()
        log(f"reference run: {N_STEPS} steps, final loss "
            f"{ref_losses[N_STEPS - 1]:.6f}")

        plan = chaos.ChaosPlan(SPEC, seed=SEED)
        chaos.install(plan)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)

            # ---- family 1: loader worker kill -> respawn ---------------
            got = [tuple(b if isinstance(b, (list, tuple)) else [b])
                   for b in DataLoader(_DrillDataset(), batch_size=2,
                                       num_workers=2)]
            if len(got) != BATCHES:
                failures.append(
                    f"loader kill: {len(got)} batches arrived, "
                    f"want {BATCHES}")
            else:
                for i, (g, r) in enumerate(zip(got, ref_batches)):
                    for ga, ra in zip(g, r):
                        if not np.allclose(np.asarray(ga.numpy()),
                                           np.asarray(ra.numpy())):
                            failures.append(
                                f"loader kill: batch {i} content drift "
                                f"after respawn")
                            break
            if not any(s == "loader.worker_kill" for s, _, _ in plan.log):
                failures.append("loader kill: fault never fired")
            log("family 1 (loader kill -> respawn): "
                f"{len(got)} batches, order preserved")

            # ---- family 2: nonfinite steps -> skip, skip, rollback -----
            mgr = CheckpointManager(root, max_to_keep=3)
            guard = NonfiniteGuard(max_consecutive=3, manager=mgr,
                                   fold_rng=False)
            model, ts = _fresh_step(guard=guard)
            chaos_losses = {}
            _drive(ts, ref_batches, 2, chaos_losses)
            mgr.save(2, train_step=ts)                      # save #1: good
            _drive(ts, ref_batches, 6, chaos_losses)  # calls 4-6 poisoned:
            #   two skips, a third trips rollback to ckpt-2, then the
            #   rewound _step makes _drive replay 3..6 clean
            if guard.total_skipped != 3 or guard.rollbacks != 1:
                failures.append(
                    f"guard: skipped={guard.total_skipped} (want 3) "
                    f"rollbacks={guard.rollbacks} (want 1)")
            log(f"family 2 (nonfinite guard): {guard.total_skipped} "
                f"skips, {guard.rollbacks} rollback, replay clean")

            # ---- family 3: torn save -> latest() falls back ------------
            try:
                mgr.save(6, train_step=ts)                  # save #2: torn
                failures.append("torn save: ChaosInterrupt not raised")
            except ChaosInterrupt:
                pass
            if mgr.latest() != mgr.path_for(2):
                failures.append(
                    f"torn save: latest()={mgr.latest()}, want ckpt-2")
            log("family 3 (torn checkpoint): latest() fell back past "
                "the torn ckpt-6")

            # ---- family 4: SIGTERM mid-save -> flagged, final save -----
            mgr.install_preemption_handler()
            try:
                mgr.save(6, train_step=ts)          # save #3: preempted
                if not mgr.preempted:
                    failures.append(
                        "preemption: SIGTERM during save not flagged")
            finally:
                mgr.uninstall_preemption_handler()
            if mgr.latest() != mgr.path_for(6):
                failures.append(
                    f"preemption: latest()={mgr.latest()}, want ckpt-6 "
                    f"(the mid-SIGTERM save must still publish)")
            log("family 4 (mid-save SIGTERM): preempted flag set, "
                "ckpt-6 published")

            # ---- resume IN THE SAME PROCESS ----------------------------
            mgr2 = CheckpointManager(root, max_to_keep=3)
            model2, ts2 = _fresh_step()
            meta = mgr2.restore(train_step=ts2)
            if meta.get("step") != 6:
                failures.append(
                    f"resume: restored step {meta.get('step')}, want 6")
            _drive(ts2, ref_batches, N_STEPS, chaos_losses)
        chaos.uninstall()

        got_w = np.asarray(model2.weight.numpy())
        if not np.allclose(got_w, ref_w, atol=1e-6):
            failures.append(
                f"resume: final weights drift "
                f"{np.abs(got_w - ref_w).max():.3e} from the "
                f"uninterrupted reference")
        for s in range(6, N_STEPS):
            if not np.isclose(chaos_losses[s], ref_losses[s], atol=1e-6):
                failures.append(
                    f"resume: loss at recovered step {s} = "
                    f"{chaos_losses[s]:.6f}, reference "
                    f"{ref_losses[s]:.6f}")
        log(f"resume: steps 6..{N_STEPS - 1} losses match the reference "
            f"exactly")
    finally:
        chaos.uninstall()
        shutil.rmtree(root, ignore_errors=True)

    if failures:
        print("chaos_check FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check OK: plan {SPEC!r} seed={SEED} — all four fault "
          f"families recovered; resumed run matches the uninterrupted "
          f"reference", file=out)
    return 0


# ========================================================= --cold-start
COLD_N_STEPS = 8    # optimizer steps in the cold-start drill
COLD_KILL_AT = 4    # "process death" after this many steps


def run_cold_worker(cache_dir, root, out=None):
    """The restarted process of the cold-start drill: restore the
    checkpoint, drive to COLD_N_STEPS against the (supposedly) warm
    cache, and report one JSON line — losses per step, final weights,
    and every cache/compile counter the parent asserts on.

    This runs in a REAL subprocess, not an in-process simulation: a
    genuine restart never holds a live instance of the executables it
    loads, which is both the scenario the cache exists for and the only
    configuration jaxlib supports (deserializing a program the same
    process already compiled is a known double-instance segfault — see
    compile_cache._MEMO)."""
    out = out if out is not None else sys.stdout
    import warnings

    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import compile_cache as cc
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.resilience.manager import CheckpointManager

    reg = MetricsRegistry()
    obs.enable(reg)
    cc.configure(cache_dir)
    batches = [tuple(b if isinstance(b, (list, tuple)) else [b])
               for b in DataLoader(_DrillDataset(), batch_size=2,
                                   num_workers=0)]
    model, ts = _fresh_step()
    mgr = CheckpointManager(root, max_to_keep=2)
    meta = mgr.restore(train_step=ts)
    losses = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", cc.CacheUnavailableWarning)
        _drive(ts, batches, COLD_N_STEPS, losses)
    stats = cc.stats()
    stats["compiles"] = sum(
        r.get("value", 0) for r in reg.snapshot()
        if r["name"] == "jit_compiles_total"
        and "TrainStep" in r["labels"].get("fn", ""))
    stats["cache_hits"] = sum(
        r.get("value", 0) for r in reg.snapshot()
        if r["name"] == "jit_persistent_cache_hits_total")
    print(json.dumps({
        "restored_step": meta.get("step"),
        "losses": {str(k): v for k, v in losses.items()},
        "weights": np.asarray(model.weight.numpy(),
                              dtype=np.float64).ravel().tolist(),
        "stats": stats,
    }), file=out, flush=True)
    return 0


def _spawn_cold_worker(cache_dir, root):
    """Run run_cold_worker in a fresh interpreter; returns (rc, report
    dict or None, raw output)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cold-start-worker",
         "--cache-dir", cache_dir, "--ckpt-root", root],
        capture_output=True, text=True, timeout=600)
    report = None
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                report = json.loads(line)
            except json.JSONDecodeError:
                pass
            break
    return proc.returncode, report, proc.stdout + proc.stderr


def run_cold_start(out=None, verbose=False):
    """The cold-start drill: train with a persistent compile cache →
    kill → restart (a REAL subprocess) with the warm cache → the
    restarted process must perform ZERO compilations (every jit entry
    loads its serialized executable) and land on bit-exact losses and
    weights vs an uninterrupted reference.  Then an injected corrupt
    cache entry must be quarantined and transparently recompiled —
    counter incremented, no crash, losses still exact."""
    out = out if out is not None else sys.stdout
    import shutil
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.jit import compile_cache as cc
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.manager import CheckpointManager

    def log(msg):
        if verbose:
            print(msg, file=out)

    cache_dir = tempfile.mkdtemp(prefix="chaos_cc_cache_")
    root = tempfile.mkdtemp(prefix="chaos_cc_ckpt_")
    reg = MetricsRegistry()
    obs.enable(reg)
    # a mesh leaked by an earlier in-process caller (e.g. the
    # mesh-change drill) would enter THIS process's compile-cache keys
    # but not the fresh restart subprocess's — every warm lookup would
    # spuriously miss; the drill keyspace must match a clean restart
    from paddle_tpu.distributed import mesh as _mesh
    prior_mesh = _mesh._state["mesh"]
    _mesh.clear_mesh()
    failures = []
    try:
        ref_batches = [tuple(b if isinstance(b, (list, tuple)) else [b])
                       for b in __import__("paddle_tpu").io.DataLoader(
                           _DrillDataset(), batch_size=2, num_workers=0)]

        def counters():
            s = cc.stats()
            s["compiles"] = sum(
                r.get("value", 0) for r in reg.snapshot()
                if r["name"] == "jit_compiles_total"
                and "TrainStep" in r["labels"].get("fn", ""))
            return s

        # ---- reference: cache disabled, plain jit ---------------------
        cc.configure(None)
        _, ref_ts = _fresh_step()
        ref_losses = {}
        _drive(ref_ts, ref_batches, COLD_N_STEPS, ref_losses)
        ref_w = np.asarray(ref_ts.model.weight.numpy(),
                           dtype=np.float64).ravel()
        log(f"reference: {COLD_N_STEPS} steps, final loss "
            f"{ref_losses[COLD_N_STEPS]:.6f}")

        # ---- phase 1: cold run with an empty cache, killed mid-way ----
        cc.configure(cache_dir)
        base = counters()
        mgr = CheckpointManager(root, max_to_keep=2)
        _, ts1 = _fresh_step()
        cold_losses = {}
        _drive(ts1, ref_batches, COLD_KILL_AT, cold_losses)
        mgr.save(COLD_KILL_AT, train_step=ts1)
        after_cold = counters()
        if after_cold["misses"] - base["misses"] < 1:
            failures.append("cold run: no cache miss recorded (the "
                            "first compile never published)")
        if after_cold["compiles"] - base["compiles"] < 1:
            failures.append("cold run: compile tracker saw no compile")
        log(f"phase 1 (cold): {COLD_KILL_AT} steps, "
            f"{after_cold['misses'] - base['misses']} miss(es) "
            f"published; killed")

        def check_continuity(tag, report, from_step=1):
            losses = report.get("losses", {})
            for s in range(from_step, COLD_N_STEPS + 1):
                got = losses.get(str(s))
                if got != ref_losses[s]:
                    failures.append(
                        f"{tag}: loss at step {s} = {got!r} != reference "
                        f"{ref_losses[s]!r} (must be bit-exact)")
            got_w = np.asarray(report.get("weights", []), dtype=np.float64)
            if not np.array_equal(got_w, ref_w):
                failures.append(f"{tag}: final weights differ (must be "
                                f"bit-exact)")
            if report.get("restored_step") != COLD_KILL_AT:
                failures.append(
                    f"{tag}: restore landed on step "
                    f"{report.get('restored_step')}, want {COLD_KILL_AT}")

        # ---- phase 2: warm restart (subprocess) — ZERO recompiles -----
        rc, report, raw = _spawn_cold_worker(cache_dir, root)
        if rc != 0 or report is None:
            failures.append(
                f"warm restart process died (rc={rc}):\n{raw[-2000:]}")
        else:
            s2 = report["stats"]
            if s2["compiles"] != 0:
                failures.append(
                    f"warm restart COMPILED {s2['compiles']} time(s) — "
                    f"the whole point is zero recompiles")
            if s2["misses"] != 0:
                failures.append(f"warm restart missed the cache "
                                f"{s2['misses']} time(s), want 0")
            if s2["hits"] < 1 or s2["cache_hits"] < 1:
                failures.append(
                    f"warm restart: hits {s2['hits']} / tracker "
                    f"cache-hits {s2['cache_hits']}, want >= 1 each")
            check_continuity("warm restart", report,
                             from_step=COLD_KILL_AT + 1)
            log(f"phase 2 (warm subprocess): 0 compiles, "
                f"{s2['hits']} cache hit(s), losses bit-exact through "
                f"step {COLD_N_STEPS}")

        # ---- phase 3: corrupt entry → quarantine + silent recompile ---
        victim = chaos.corrupt_cache_entry(cache_dir, mode="flip")
        rc, report, raw = _spawn_cold_worker(cache_dir, root)
        if rc != 0 or report is None:
            failures.append(
                f"corrupt-entry restart CRASHED (rc={rc}) — quarantine "
                f"must degrade, never abort:\n{raw[-2000:]}")
        else:
            s3 = report["stats"]
            if s3["quarantined"] < 1:
                failures.append(
                    "corrupt entry was NOT quarantined (counter "
                    "unchanged)")
            if s3["misses"] < 1:
                failures.append(
                    "corrupt entry: no silent recompile after quarantine")
            check_continuity("corrupt-recovery", report,
                             from_step=COLD_KILL_AT + 1)
            log(f"phase 3 (corrupt): {os.path.basename(victim)} "
                f"quarantined, recompiled silently, losses exact")
    finally:
        obs.disable()
        cc.reset()
        if prior_mesh is not None:
            _mesh.set_mesh(prior_mesh)
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(root, ignore_errors=True)

    if failures:
        print("chaos_check --cold-start FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check --cold-start OK: warm-cache restart performed "
          f"zero recompiles with bit-exact loss continuity; corrupt "
          f"entry quarantined + silently recompiled", file=out)
    return 0


# ======================================================== --mesh-change
MESH_N_STEPS = 8    # optimizer steps in the elastic drill
MESH_KILL_AT = 6    # restart.mesh_change fires on this fleet-step call
MESH_SPEC = f"restart.mesh_change@{MESH_KILL_AT}"
MESH_TOL = 1e-5     # dp=4 vs dp=2 reduction-grouping tolerance


def _fleet_step(dp, stage=3, seed=1234):
    """Fresh dp-mesh fleet engine (ZeRO `stage` so params are genuinely
    sharded over dp and a world-size change is a real redistribution)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "sharding_stage": stage}
    fleet.fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(seed)
    model = nn.Linear(4, 2)
    o = opt.Momentum(learning_rate=0.05, momentum=0.9,
                     parameters=model.parameters())

    def loss_fn(m, x, y):
        return ((m(x) - y) ** 2).mean()

    return model, fleet.fleet.build_train_step(model, loss_fn, o)


def run_mesh_change(out=None, verbose=False):
    """The elastic restart drill: 4-device train → chaos kill → 2-device
    resume via device-side resharding → loss-trajectory continuity, plus
    a retried collective.timeout along the resumed run."""
    out = out if out is not None else sys.stdout
    import shutil
    import tempfile
    import warnings

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.observability import metrics
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.chaos import ChaosInterrupt
    from paddle_tpu.resilience.manager import CheckpointManager

    def log(msg):
        if verbose:
            print(msg, file=out)

    import jax
    if jax.device_count() < 4:
        print(f"chaos_check --mesh-change needs >= 4 devices, have "
              f"{jax.device_count()} (set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 before jax "
              f"imports)", file=out)
        return 1

    reg = metrics.registry()

    def counter_val(name, **labels):
        return reg.counter(name, **labels).value

    base_device = counter_val("resilience_mesh_reshard_total",
                              path="device")
    base_host = counter_val("resilience_mesh_reshard_total",
                            path="host_fallback")
    base_arrays = counter_val("reshard_arrays_total", path="device")
    base_retry = counter_val("collective_retry_total", op="all_reduce")
    base_tmo = counter_val("collective_timeout_total", op="all_reduce")

    rs = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs.randn(8, 4).astype("float32")),
                paddle.to_tensor(rs.randn(8, 2).astype("float32")))
               for _ in range(8)]

    root = tempfile.mkdtemp(prefix="chaos_mesh_")
    failures = []
    try:
        # ---- reference: uninterrupted run on the 4-device mesh --------
        model_r, ts_r = _fleet_step(dp=4)
        ref_losses = [float(ts_r(*batches[i % len(batches)]).numpy())
                      for i in range(MESH_N_STEPS)]
        ref_w = np.asarray(ts_r.model.weight.numpy()).copy()
        log(f"reference (dp=4, uninterrupted): final loss "
            f"{ref_losses[-1]:.6f}")

        # ---- phase 1: train on dp=4, chaos kills the fleet -----------
        model_c, ts_c = _fleet_step(dp=4)
        mgr = CheckpointManager(root, max_to_keep=3)
        plan = chaos.install(chaos.ChaosPlan(MESH_SPEC))
        chaos_losses = {}
        killed = False
        try:
            for i in range(MESH_N_STEPS):
                chaos_losses[i] = float(
                    ts_c(*batches[i % len(batches)]).numpy())
                mgr.save(ts_c._step, train_step=ts_c)
        except ChaosInterrupt:
            killed = True
        finally:
            chaos.uninstall()
        if not killed:
            failures.append("restart.mesh_change never killed the fleet")
        killed_at = max(chaos_losses, default=-1) + 1
        log(f"phase 1 (dp=4): killed after step {killed_at}, "
            f"latest ckpt {mgr.latest()}")

        # ---- phase 2: restart on dp=2, reshard device-side -----------
        # different init seed on purpose: every weight must come from
        # the retained checkpoint, not from a lucky re-init
        model_2, ts_2 = _fleet_step(dp=2, seed=999)
        mgr2 = CheckpointManager(root, max_to_keep=3)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            meta = mgr2.restore(train_step=ts_2)
        resumed = int(meta.get("step", -1))
        if resumed != killed_at:
            failures.append(
                f"resume: restored step {resumed}, want {killed_at}")
        d_device = counter_val("resilience_mesh_reshard_total",
                               path="device") - base_device
        d_host = counter_val("resilience_mesh_reshard_total",
                             path="host_fallback") - base_host
        d_arrays = counter_val("reshard_arrays_total",
                               path="device") - base_arrays
        if d_device != 1 or d_host != 0:
            failures.append(
                f"reshard route: resilience_mesh_reshard_total "
                f"path=device +{d_device} / path=host_fallback "
                f"+{d_host}, want +1 / +0 (the device path, not the "
                f"replicated host bounce)")
        if d_arrays <= 0:
            failures.append(
                "reshard route: no arrays moved through the device path")
        log(f"phase 2 (dp=2): restored step {resumed}; {d_arrays} "
            f"arrays resharded device-side")

        # ---- phase 3: finish the run; one collective times out -------
        coll.configure_collectives(timeout=30.0, retries=2,
                                   backoff_base=0.01)
        chaos.install(chaos.ChaosPlan("collective.timeout@1"))
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for i in range(resumed, MESH_N_STEPS):
                    loss = ts_2(*batches[i % len(batches)])
                    # an eager cross-replica sync (identity in value on
                    # a single controller): the injected timeout lands
                    # here and must be absorbed by the retry policy
                    loss = dist.all_reduce(loss)
                    chaos_losses[i] = float(loss.numpy())
        finally:
            chaos.uninstall()
            coll.configure_collectives()      # clear the policy
        d_tmo = counter_val("collective_timeout_total",
                            op="all_reduce") - base_tmo
        d_retry = counter_val("collective_retry_total",
                              op="all_reduce") - base_retry
        if d_tmo < 1 or d_retry < 1:
            failures.append(
                f"collective.timeout: timeout_total +{d_tmo} / "
                f"retry_total +{d_retry}, want >= 1 each (the policy "
                f"must retry, not the supervisor)")
        log(f"phase 3: run completed; collective.timeout retried "
            f"({d_retry} retries)")

        # ---- continuity: post-restore trajectory matches reference ---
        for s in range(MESH_N_STEPS):
            got = chaos_losses.get(s)
            if got is None:
                failures.append(
                    f"continuity: step {s} was never executed "
                    f"(resume landed past it)")
            elif abs(got - ref_losses[s]) > MESH_TOL:
                failures.append(
                    f"continuity: loss at step {s} = {got:.6f}, "
                    f"reference {ref_losses[s]:.6f} (tol {MESH_TOL})")
        got_w = np.asarray(ts_2.model.weight.numpy())
        if not np.allclose(got_w, ref_w, atol=1e-6):
            failures.append(
                f"continuity: final weights drift "
                f"{np.abs(got_w - ref_w).max():.3e} from the "
                f"uninterrupted dp=4 reference")
        log(f"continuity: steps 0..{MESH_N_STEPS - 1} within {MESH_TOL} "
            f"of the reference")
    finally:
        chaos.uninstall()
        # _fleet_step installed a global mesh; a leaked one would leak
        # into the mesh fingerprint of every later jit entry in this
        # process (e.g. the cold-start drill's compile-cache keys)
        from paddle_tpu.distributed import mesh as _mesh
        _mesh.clear_mesh()
        shutil.rmtree(root, ignore_errors=True)

    if failures:
        print("chaos_check --mesh-change FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check --mesh-change OK: dp=4 run killed on fleet-step "
          f"call {MESH_KILL_AT}, resumed on dp=2 via device-side "
          f"resharding; "
          f"loss trajectory within {MESH_TOL} of the uninterrupted "
          f"reference; injected collective.timeout retried by the "
          f"policy", file=out)
    return 0


def run_serving(out=None, verbose=False):
    """The serving overload drill: a pool deliberately too small for the
    offered load, plus injected exhaustion (`serving.pool_exhausted`) and
    one poisoned request (`serving.request_poison`).  Green means the
    continuous-batching engine preempted and resumed under pressure with
    every surviving request's tokens IDENTICAL to a sequential
    `generate()` reference, the poisoned request failed alone, and the
    pool came back whole — zero leaked blocks, zero bad refcounts."""
    out = out if out is not None else sys.stdout
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.observability import metrics
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    from paddle_tpu.text.generation import generate

    def log(msg):
        if verbose:
            print(msg, file=out)

    failures = []
    reg = metrics.registry()
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, 64, size=n).tolist()
               for n in (9, 5, 12, 7, 4, 10, 6, 8)]
    new_tokens = 8
    refs = [generate(model, paddle.to_tensor(np.asarray([p], "int64")),
                     max_new_tokens=new_tokens).numpy()[0, len(p):].tolist()
            for p in prompts]

    base_pre = reg.counter("serving_requests_preempted_total").value
    base_exh = reg.counter("serving_pool_exhausted_total").value
    base_fail = reg.counter("serving_requests_failed_total").value

    # pool of 7 x 4-token blocks serves 8 requests needing ~2-5 blocks
    # each -> genuine overload; the chaos spec injects 3 EXTRA refusals
    # mid-run and poisons the 3rd submitted request
    with chaos.scoped("serving.pool_exhausted@6*3;"
                      "serving.request_poison@3"):
        eng = LLMEngine(model, num_blocks=7, block_size=4, max_running=8,
                        prefill_chunk=16)
        reqs = [eng.add_request(p, max_new_tokens=new_tokens)
                for p in prompts]
        eng.run(max_steps=10_000)

    poisoned = [r for r in reqs if r.poisoned]
    if len(poisoned) != 1 or poisoned[0] is not reqs[2]:
        failures.append(f"expected exactly request #2 poisoned, got "
                        f"{[r.id for r in poisoned]}")
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        if req.poisoned:
            if req.finish_reason != "error":
                failures.append(
                    f"poisoned request {i} finished {req.finish_reason!r},"
                    f" expected 'error'")
            continue
        if req.finish_reason not in ("eos", "length"):
            failures.append(f"request {i} ended {req.finish_reason!r}")
        if list(req.generated) != ref:
            failures.append(
                f"request {i} tokens diverged after "
                f"{req.preemptions} preemption(s): {req.generated} "
                f"vs sequential {ref}")
    n_pre = reg.counter("serving_requests_preempted_total").value - base_pre
    n_exh = reg.counter("serving_pool_exhausted_total").value - base_exh
    n_fail = reg.counter("serving_requests_failed_total").value - base_fail
    log(f"preemptions={n_pre} exhaustions={n_exh} failed={n_fail}")
    if n_pre < 1:
        failures.append("overload never triggered a preemption — the "
                        "drill pool is not actually under pressure")
    if n_exh < 3:
        failures.append(f"injected pool exhaustion did not fire 3 times "
                        f"(saw {n_exh})")
    if n_fail != 1:
        failures.append(f"expected exactly 1 failed (poisoned) request, "
                        f"counters saw {n_fail}")
    leaked, bad = eng.pool.check_leaks()
    if leaked or bad:
        failures.append(f"block pool leaked: refcount>0 {leaked}, "
                        f"refcount<0 {bad}")
    if eng.pool.free_blocks != eng.pool.num_blocks:
        failures.append(f"free list short after drain: "
                        f"{eng.pool.free_blocks}/{eng.pool.num_blocks}")

    if failures:
        print("chaos_check --serving FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check --serving OK: 8 requests over a 7-block pool, "
          f"{n_pre} preemption(s) + 3 injected exhaustions + 1 poisoned "
          f"request; every survivor token-identical to sequential "
          f"generate(), poisoned request failed alone, zero block leaks",
          file=out)
    return 0


# ============================================================= --router
def run_router(out=None, verbose=False):
    """The serving-tier survival drill (three phases over a 2-replica
    router; one shared tiny GPT so replicas are weight-identical):

    1. **kill + failover + crash-loop**: ``serving.replica_kill`` kills
       replica r0 mid-stream three times (respawned through the backoff
       policy between deaths).  Every orphaned request must fail over
       to r1 and finish with a token stream BYTE-IDENTICAL to the
       uninterrupted sequential `generate()` reference — the router's
       failover-overlap dedup must fire (proof the resumed stream was
       consistency-checked, not blindly trusted), the third death must
       trip the crash-loop detector (r0 ABANDONED, not burned in
       restarts), and the survivor's pool must come back leak-free.
    2. **overload shedding**: with r0 gone, a submission burst against
       r1's queue-depth watermark must split into fast structured
       refusals (ShedRequest with reason + gauge detail, nothing
       allocated) and admitted requests that all complete.
    3. **hang**: ``serving.replica_hang`` wedges r0 (no stepping, no
       heartbeat).  The router must detect the stale beat within the
       configured timeout on its own clock, evict with cause="hang"
       (NOT "crash"), fail the work over, and still match every
       reference stream.
    """
    out = out if out is not None else sys.stdout
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.observability import metrics
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.backoff import Backoff
    from paddle_tpu.serving import LLMEngine, Router, ShedRequest
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    from paddle_tpu.text.generation import generate

    def log(msg):
        if verbose:
            print(msg, file=out)

    failures = []
    reg = metrics.registry()
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 64, size=n).tolist()
               for n in (9, 5, 12, 7, 4, 10)]
    new_tokens = 16
    refs = [generate(model, paddle.to_tensor(np.asarray([p], "int64")),
                     max_new_tokens=new_tokens)
            .numpy()[0, len(p):].tolist() for p in prompts]

    def factory():
        return LLMEngine(model, num_blocks=24, block_size=4,
                         max_running=8, prefill_chunk=16,
                         shed_queue_depth=3)

    def counter(name, **labels):
        return reg.counter(name, **labels).value

    base = {n: counter(n) for n in (
        "router_failover_requests_total", "router_failover_dedup_total",
        "router_failover_token_mismatch_total", "router_respawns_total",
        "router_crash_loop_aborts_total")}
    base_evict = {c: counter("router_replica_evicted_total", cause=c)
                  for c in ("crash", "hang")}

    # ---- phase 1: kill r0 three times -> failover + crash-loop abort --
    with chaos.scoped("serving.replica_kill@4#r0;"
                      "serving.replica_kill@6#r0;"
                      "serving.replica_kill@8#r0"):
        router = Router(factory, replicas=2, heartbeat_timeout=5.0,
                        respawn=True,
                        backoff=Backoff(base=0.001, factor=2.0,
                                        max_delay=0.01),
                        crash_loop_threshold=3, crash_loop_window=60.0)
        reqs = [router.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        router.run(max_steps=100_000)
    for i, (rr, ref) in enumerate(zip(reqs, refs)):
        if rr.state != "finished":
            failures.append(f"kill: request {i} ended "
                            f"{rr.state}/{rr.finish_reason!r}")
        elif rr.emitted != ref:
            failures.append(
                f"kill: request {i} stream diverged after "
                f"{rr.failovers} failover(s): {rr.emitted} vs "
                f"sequential {ref}")
    n_failover = counter("router_failover_requests_total") \
        - base["router_failover_requests_total"]
    n_dedup = counter("router_failover_dedup_total") \
        - base["router_failover_dedup_total"]
    n_mismatch = counter("router_failover_token_mismatch_total") \
        - base["router_failover_token_mismatch_total"]
    n_crash = counter("router_replica_evicted_total", cause="crash") \
        - base_evict["crash"]
    n_respawn = counter("router_respawns_total") \
        - base["router_respawns_total"]
    n_abort = counter("router_crash_loop_aborts_total") \
        - base["router_crash_loop_aborts_total"]
    if n_failover < 1:
        failures.append("kill: no request ever failed over — the kill "
                        "missed every in-flight stream")
    if n_dedup < 1:
        failures.append(
            "kill: failover dedup never fired — no stream was killed "
            "MID-token (resume started before any emission)")
    if n_mismatch:
        failures.append(f"kill: {n_mismatch} failover overlap token(s) "
                        f"MISMATCHED the already-emitted stream")
    if n_crash != 3 or n_respawn != 2 or n_abort != 1:
        failures.append(
            f"kill: evictions/respawns/aborts = {n_crash}/{n_respawn}/"
            f"{n_abort}, want 3/2/1 (three deaths, two backoff "
            f"respawns, then the crash-loop detector must abandon)")
    states = {s.name: s.state for s in router._slots}
    if states.get("r0") != "abandoned":
        failures.append(f"kill: r0 state {states.get('r0')!r} after 3 "
                        f"crashes, want 'abandoned'")
    log(f"phase 1 (kill x3): {n_failover} failover(s), {n_dedup} "
        f"dedup(s), {n_crash} evictions, {n_respawn} respawns, "
        f"{n_abort} crash-loop abort; streams identical")

    # ---- phase 2: overload burst against the survivor's watermark ----
    base_shed = counter("serving_requests_shed_total",
                        reason="queue_depth")
    admitted, shed = [], []
    for i in range(10):
        try:
            admitted.append(router.submit(prompts[i % len(prompts)],
                                          max_new_tokens=4))
        except ShedRequest as e:
            shed.append(e)
    router.run(max_steps=100_000)
    if not shed:
        failures.append("shed: burst past the queue-depth watermark "
                        "was never refused")
    for e in shed:
        if e.reason != "queue_depth" or "queue_depth" not in e.detail:
            failures.append(f"shed: refusal not structured: "
                            f"reason={e.reason!r} detail={e.detail}")
            break
    d_shed = counter("serving_requests_shed_total",
                     reason="queue_depth") - base_shed
    if d_shed != len(shed):
        failures.append(f"shed: counter saw {d_shed} refusals, router "
                        f"raised {len(shed)}")
    for i, rr in enumerate(admitted):
        if rr.state != "finished":
            failures.append(f"shed: admitted burst request {i} ended "
                            f"{rr.state}/{rr.finish_reason!r}")
    leaks = router.close()
    for name, (leaked, bad) in leaks.items():
        if leaked or bad:
            failures.append(f"survivor {name} pool leaked: rc>0 "
                            f"{leaked}, rc<0 {bad}")
    log(f"phase 2 (overload burst): {len(admitted)} admitted + "
        f"{len(shed)} shed with structured reasons; survivor leak-free")

    # ---- phase 3: hang -> stale heartbeat -> evict within timeout ----
    hb_timeout = 0.3
    with chaos.scoped("serving.replica_hang@3#r0"):
        router2 = Router(factory, replicas=2,
                         heartbeat_timeout=hb_timeout, respawn=False)
        reqs2 = [router2.submit(p, max_new_tokens=new_tokens)
                 for p in prompts[:4]]
        t0 = time.monotonic()
        router2.run(max_steps=1_000_000)
    hangs = [e for e in router2.events
             if e["event"] == "evict" and e["cause"] == "hang"]
    crashes = [e for e in router2.events
               if e["event"] == "evict" and e["cause"] == "crash"]
    if len(hangs) != 1 or crashes:
        failures.append(f"hang: evictions hang={len(hangs)} "
                        f"crash={len(crashes)}, want exactly one HANG "
                        f"(stale beat), zero crashes")
    else:
        # detection must land within the timeout (+ scheduling slack)
        silent = hangs[0].get("silent_for")
        if silent is None or silent > hb_timeout + 1.0:
            failures.append(
                f"hang: evicted after {silent!r}s of silence, want "
                f"within timeout {hb_timeout}s (+1s step slack)")
    for i, (rr, ref) in enumerate(zip(reqs2, refs[:4])):
        if rr.state != "finished" or rr.emitted != ref:
            failures.append(
                f"hang: request {i} {rr.state}/{rr.finish_reason!r} "
                f"stream {'ok' if rr.emitted == ref else 'DIVERGED'}")
    leaks2 = router2.close()
    for name, (leaked, bad) in leaks2.items():
        if leaked or bad:
            failures.append(f"hang survivor {name} pool leaked: "
                            f"rc>0 {leaked}, rc<0 {bad}")
    log(f"phase 3 (hang): stale beat detected after "
        f"{hangs[0]['silent_for']:.3f}s (timeout {hb_timeout}s), "
        f"evicted as hang, streams identical" if hangs else
        "phase 3 (hang): FAILED")

    if failures:
        print("chaos_check --router FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check --router OK: replica killed 3x mid-stream -> "
          f"{n_failover} failover(s) with overlap-dedup consistency "
          f"checks, 2 backoff respawns + crash-loop abandon; overload "
          f"burst shed {len(shed)} request(s) with structured reasons; "
          f"hung replica evicted via stale heartbeat within "
          f"{hb_timeout}s; every surviving stream byte-identical to "
          f"the sequential reference, zero leaked blocks on survivors",
          file=out)
    return 0


# ====================================================== --router --proc
PROC_BUDGET_S = 480.0   # wall-clock guard: the drill must leave the
                        # rest of tier-1 room inside the 870 s timeout


def run_router_proc(out=None, verbose=False):
    """The process-per-replica survival drill — the --router drill with
    REAL processes and REAL ``kill -9``:

    1. **SIGKILL x3 + failover + crash-loop**: two worker processes
       (AOT-warm-started through the PR-8 artifact path when this jax
       can serialize executables) serve 6 streams; worker r0 is
       ``kill -9``'d mid-stream, respawned through the backoff policy,
       killed twice more → the third death trips the crash-loop
       detector (ABANDONED).  Every surviving stream must be
       byte-identical to the sequential `generate()` reference (the
       overlap dedup proving the resumed streams were consistency-
       checked), each death must land in
       ``router_worker_exits_total{signal=SIGKILL}``, and the
       survivor's pool must come back leak-free over the wire.
    2. **transport damage**: ``serving.transport_drop`` tears a frame
       on r0's channel mid-stream — the transport must reject the
       stream structurally (FrameError, counted), the router must
       evict r0 as a crash and fail its streams over, and every stream
       must STILL match the reference (a dropped frame may never
       become a silent token gap).

    After each phase, close() must leave **zero orphaned worker
    processes** — every spawned pid dead AND reaped.
    """
    out = out if out is not None else sys.stdout
    import shutil
    import signal as _signal
    import tempfile
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.observability import metrics
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.backoff import Backoff
    from paddle_tpu.serving import (LLMEngine, Router,
                                    export_serving_artifacts)
    from paddle_tpu.serving import worker as sw
    from paddle_tpu.serving.transport import TransportPolicy
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    from paddle_tpu.text.generation import generate

    def log(msg):
        if verbose:
            print(msg, file=out)

    # two worker processes, and a parent that builds the reference model
    # and the AOT artifacts itself: fine on the CPU, impossible on a TPU
    # host, where a chip belongs to one process — refuse before spawning
    sw.check_proc_replicas(2)

    t_start = time.monotonic()
    failures = []
    reg = metrics.registry()

    def counter(name, **labels):
        return reg.counter(name, **labels).value

    cfg_kw = dict(vocab_size=64, hidden_size=32, num_layers=2,
                  num_heads=4, max_position_embeddings=64,
                  hidden_dropout=0.0, attention_dropout=0.0,
                  tensor_parallel=False)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(**cfg_kw))
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 64, size=n).tolist()
               for n in (9, 5, 12, 7, 4, 10)]
    new_tokens = 16
    refs = [generate(model, paddle.to_tensor(np.asarray([p], "int64")),
                     max_new_tokens=new_tokens)
            .numpy()[0, len(p):].tolist() for p in prompts]

    eng_kw = dict(num_blocks=24, block_size=4, max_running=8,
                  prefill_chunk=16)
    aot_dir = tempfile.mkdtemp(prefix="chaos_proc_aot_")
    aot_ok = False
    pids = []
    # a mesh leaked by an earlier in-process caller would be stamped
    # into the AOT artifacts, and the mesh-less workers would refuse
    # them ("mesh topology"): export from the state a worker starts in
    from paddle_tpu.distributed import mesh as _mesh
    prior_mesh = _mesh._state["mesh"]
    _mesh.clear_mesh()
    try:
        # AOT artifacts exported ONCE so every worker — and every
        # backoff respawn — warm-starts through the PR-8 path
        exp_eng = LLMEngine(model, **eng_kw)
        try:
            export_serving_artifacts(exp_eng, aot_dir,
                                     prompt_lens=[len(p)
                                                  for p in prompts])
            aot_ok = True
        except Exception as e:
            log(f"AOT export unavailable ({e}); workers compile live")
        exp_eng.close()

        # workers re-derive the same weights: seed 0 + the same config,
        # step_delay throttles them so streams stay open long enough
        # for a deterministic mid-stream kill
        spec = sw.gpt_spec(config=cfg_kw, seed=0, engine=eng_kw,
                           load_aot=aot_dir if aot_ok else None,
                           step_delay_s=0.01)
        pol = TransportPolicy(timeout=60.0, retries=1,
                              backoff_base=0.05)

        def replica_factory(name, hb_path, respawning=False):
            h = sw.ProcReplica(spec, name, hb_path, policy=pol)
            pids.append(h.proc.pid)
            return h

        def assert_no_orphans(tag):
            for pid in pids:
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    continue         # dead AND reaped (zombies answer 0)
                failures.append(f"{tag}: worker pid {pid} survived "
                                f"close() — orphan process")

        base = {n: counter(n) for n in (
            "router_failover_requests_total",
            "router_failover_dedup_total",
            "router_failover_token_mismatch_total",
            "router_respawns_total", "router_crash_loop_aborts_total",
            "router_transport_frame_errors_total")}
        base_crash = counter("router_replica_evicted_total",
                             cause="crash")
        base_kill9 = counter("router_worker_exits_total",
                             signal="SIGKILL")

        # ---- phase 1: kill -9 x3 → failover, respawn, abandon --------
        router = Router(None, replicas=2, heartbeat_timeout=8.0,
                        spawn_grace_s=120.0, respawn=True,
                        backoff=Backoff(base=0.05, factor=2.0,
                                        max_delay=0.2),
                        crash_loop_threshold=3, crash_loop_window=600.0,
                        replica_factory=replica_factory)
        if not router.wait_ready(timeout=240.0):
            failures.append("phase 1: workers never became ready")
        reqs = [router.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        killed = set()       # pids SIGKILL'd: one kill per worker
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            router.step()
            slot0 = router._slots[0]
            if len(killed) < 3 and slot0.state == "healthy" \
                    and getattr(slot0.handle, "ready", False) \
                    and slot0.handle.proc.pid not in killed:
                live0 = [rr for rr in router._requests
                         if rr.state == "live" and rr.slot is slot0]
                mid_stream = any(len(rr.emitted) >= 2 for rr in live0)
                # the FIRST kill must land mid-stream (that is the
                # drill); later kills take the respawned replica
                # whenever it is back up, streams or not — like real
                # hardware (pid-gated: SIGKILL delivery is async, the
                # same dying worker must not soak up all three)
                if mid_stream or killed:
                    os.kill(slot0.handle.proc.pid, _signal.SIGKILL)
                    killed.add(slot0.handle.proc.pid)
            if not router.has_work and len(killed) >= 3 \
                    and slot0.state in ("abandoned", "dead"):
                break
        kills = len(killed)
        for i, (rr, ref) in enumerate(zip(reqs, refs)):
            if rr.state != "finished":
                failures.append(f"kill: request {i} ended "
                                f"{rr.state}/{rr.finish_reason!r}")
            elif rr.emitted != ref:
                failures.append(
                    f"kill: request {i} stream diverged after "
                    f"{rr.failovers} failover(s): {rr.emitted} vs "
                    f"sequential {ref}")
        n_failover = counter("router_failover_requests_total") \
            - base["router_failover_requests_total"]
        n_dedup = counter("router_failover_dedup_total") \
            - base["router_failover_dedup_total"]
        n_mismatch = counter("router_failover_token_mismatch_total") \
            - base["router_failover_token_mismatch_total"]
        n_crash = counter("router_replica_evicted_total",
                          cause="crash") - base_crash
        n_respawn = counter("router_respawns_total") \
            - base["router_respawns_total"]
        n_abort = counter("router_crash_loop_aborts_total") \
            - base["router_crash_loop_aborts_total"]
        n_kill9 = counter("router_worker_exits_total",
                          signal="SIGKILL") - base_kill9
        if kills != 3:
            failures.append(f"kill: only delivered {kills}/3 SIGKILLs "
                            f"before the deadline")
        if n_failover < 1:
            failures.append("kill: no request ever failed over — the "
                            "kill missed every in-flight stream")
        if n_dedup < 1:
            failures.append(
                "kill: failover dedup never fired — no stream was "
                "killed MID-token (resume started before any emission)")
        if n_mismatch:
            failures.append(f"kill: {n_mismatch} failover overlap "
                            f"token(s) MISMATCHED the emitted stream")
        if n_crash != 3 or n_respawn != 2 or n_abort != 1:
            failures.append(
                f"kill: evictions/respawns/aborts = {n_crash}/"
                f"{n_respawn}/{n_abort}, want 3/2/1")
        if n_kill9 != 3:
            failures.append(
                f"kill: router_worker_exits_total{{signal=SIGKILL}} "
                f"+{n_kill9}, want +3 (every death must be attributed "
                f"to its waitpid signal)")
        if router._slots[0].state != "abandoned":
            failures.append(f"kill: r0 state "
                            f"{router._slots[0].state!r} after 3 "
                            f"SIGKILLs, want 'abandoned'")
        survivor = router._slots[1].handle
        if aot_ok and survivor is not None:
            n_aot = (survivor.ready_info or {}).get("aot_loaded", 0)
            if n_aot < 1:
                failures.append(
                    f"kill: survivor loaded {n_aot} AOT programs — "
                    f"workers must warm-start through the artifact "
                    f"path")
        if survivor is not None:
            snap = {r["name"] for r in survivor.metrics_snapshot()}
            if "serving_tokens_generated_total" not in snap:
                failures.append("kill: worker metrics_snapshot RPC "
                                "returned no serving counters")
        leaks = router.close()
        for name, (leaked, bad) in leaks.items():
            # strict ==[]: ProcReplica.close() reports (None, None) when
            # the worker could not answer — UNKNOWN is not known-clean
            if leaked != [] or bad != []:
                failures.append(f"kill survivor {name} leak report "
                                f"{leaked!r}/{bad!r}, want []/[] "
                                f"(None = worker never reported)")
        assert_no_orphans("kill")
        log(f"phase 1 (kill -9 x3): {n_failover} failover(s), "
            f"{n_dedup} dedup(s), {n_crash}/{n_respawn}/{n_abort} "
            f"evict/respawn/abandon, {n_kill9} SIGKILL exits; streams "
            f"identical; no orphans")

        # ---- phase 2: frame dropped in transit → evict + failover ----
        # frame ordinal on r0's parent-side channel: past ready + the
        # add_request replies, into the token/step stream
        with chaos.scoped("serving.transport_drop@12#r0"):
            router2 = Router(None, replicas=2, heartbeat_timeout=8.0,
                             spawn_grace_s=120.0, respawn=False,
                             replica_factory=replica_factory)
            if not router2.wait_ready(timeout=240.0):
                failures.append("drop: workers never became ready")
            reqs2 = [router2.submit(p, max_new_tokens=new_tokens)
                     for p in prompts]
            deadline = time.monotonic() + 240.0
            while router2.has_work and time.monotonic() < deadline:
                router2.step()
        n_fe = counter("router_transport_frame_errors_total") \
            - base["router_transport_frame_errors_total"]
        drops = [e for e in router2.events
                 if e["event"] == "evict" and e["cause"] == "crash"
                 and "transport_drop" in str(e.get("error"))]
        if n_fe < 1 or not drops:
            failures.append(
                f"drop: frame_errors +{n_fe}, transport-drop "
                f"evictions {len(drops)} — the torn frame must be "
                f"rejected structurally and evict the replica")
        for i, (rr, ref) in enumerate(zip(reqs2, refs)):
            if rr.state != "finished" or rr.emitted != ref:
                failures.append(
                    f"drop: request {i} {rr.state}/"
                    f"{rr.finish_reason!r} stream "
                    f"{'ok' if rr.emitted == ref else 'DIVERGED'} — a "
                    f"dropped frame may never become a token gap")
        leaks2 = router2.close()
        for name, (leaked, bad) in leaks2.items():
            if leaked != [] or bad != []:
                failures.append(f"drop survivor {name} leak report "
                                f"{leaked!r}/{bad!r}, want []/[] "
                                f"(None = worker never reported)")
        assert_no_orphans("drop")
        log(f"phase 2 (transport_drop): {n_fe} frame error(s), "
            f"{len(drops)} eviction(s); streams identical; no orphans")
    finally:
        chaos.uninstall()
        # defensive sweep: the asserts above already proved no orphans
        # on the green path; a FAILED drill must not leak processes
        # into the test session either
        for pid in pids:
            try:
                os.kill(pid, _signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        shutil.rmtree(aot_dir, ignore_errors=True)
        if prior_mesh is not None:
            _mesh.set_mesh(prior_mesh)

    elapsed = time.monotonic() - t_start
    if elapsed > PROC_BUDGET_S:
        failures.append(
            f"time budget: drill took {elapsed:.0f}s > "
            f"{PROC_BUDGET_S:.0f}s — it would crowd out the rest of "
            f"tier-1 (spawns too slow / a wait wedged)")

    if failures:
        print("chaos_check --router --proc FAILED:", file=out)
        for f in failures:
            print(f"  - {f}", file=out)
        return 1
    print(f"chaos_check --router --proc OK ({elapsed:.0f}s): worker "
          f"process kill -9'd 3x ({n_kill9} SIGKILL exits) -> "
          f"{n_failover} failover(s) with overlap dedup, 2 backoff "
          f"respawns + crash-loop abandon; injected transport frame "
          f"drop rejected structurally and evicted; every surviving "
          f"stream byte-identical to the sequential reference, zero "
          f"leaked blocks on survivors, zero orphaned workers",
          file=out)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("--mesh-change", action="store_true",
                    help="run the elastic restart drill (4-device train "
                         "-> kill -> 2-device reshard resume) instead of "
                         "the 4-family plan")
    ap.add_argument("--cold-start", action="store_true",
                    help="run the compile-cache cold-start drill (train "
                         "-> kill -> warm-cache restart with zero "
                         "recompiles; corrupt entry -> quarantine) "
                         "instead of the 4-family plan")
    ap.add_argument("--serving", action="store_true",
                    help="run the serving overload drill (pool too small "
                         "+ injected exhaustion + poisoned request; "
                         "preempted requests must finish token-identical "
                         "to sequential generate() with zero block "
                         "leaks) instead of the 4-family plan")
    ap.add_argument("--router", action="store_true",
                    help="run the serving-tier survival drill (2-replica "
                         "router; replica killed 3x mid-stream -> "
                         "failover re-prefill + crash-loop abandon, "
                         "overload burst -> structured shedding, hung "
                         "replica -> stale-heartbeat eviction; all "
                         "surviving streams must be byte-identical to "
                         "the sequential reference) instead of the "
                         "4-family plan")
    ap.add_argument("--proc", action="store_true",
                    help="with --router: run the PROCESS-per-replica "
                         "drill instead — real worker processes, real "
                         "kill -9 mid-stream (3x -> failover + backoff "
                         "respawn + crash-loop abandon), injected "
                         "transport frame drop, zero orphaned workers "
                         "after close()")
    ap.add_argument("--cold-start-worker", action="store_true",
                    help=argparse.SUPPRESS)   # the drill's restarted proc
    ap.add_argument("--cache-dir", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-root", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.cold_start_worker:
        return run_cold_worker(args.cache_dir, args.ckpt_root)
    if args.router and args.proc:
        return run_router_proc(verbose=args.verbose)
    if args.router:
        return run_router(verbose=args.verbose)
    if args.serving:
        return run_serving(verbose=args.verbose)
    if args.cold_start:
        return run_cold_start(verbose=args.verbose)
    if args.mesh_change:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            # before any jax import: the drill needs a multi-device CPU
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return run_mesh_change(verbose=args.verbose)
    return run(verbose=args.verbose)


if __name__ == "__main__":
    sys.exit(main())
