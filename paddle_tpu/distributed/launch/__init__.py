"""paddle_tpu.distributed.launch — the multi-host process runner.

Reference: python/paddle/distributed/launch/ (`python -m
paddle.distributed.launch --nnodes ... train.py`), which sets up
per-rank env, starts workers, watches them, and supports elastic
restart.  TPU-native shape: ONE controller process per host (XLA drives
every local chip), so `--nproc_per_node` > 1 is for CPU-mesh testing
and is refused on a TPU host; ranks coordinate through
jax.distributed.initialize (gRPC coordinator at `--master`), which
`paddle_tpu.distributed.init_parallel_env()` reads from the PT_*
variables this launcher exports.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time


def _parse_args(argv):
    import argparse
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch distributed training "
                    "(reference: paddle.distributed.launch)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of hosts")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PT_NODE_RANK", "0")),
                   help="this host's index")
    p.add_argument("--master", default=os.environ.get("PT_MASTER",
                                                      "127.0.0.1:8476"),
                   help="coordinator ip:port (rank-0 host)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 for TPU single-controller)")
    p.add_argument("--log_dir", default=None,
                   help="per-rank stdout/stderr capture directory")
    p.add_argument("--cache_dir", default=None,
                   help="shared persistent compile-cache directory "
                        "(exported as PADDLE_TPU_CACHE_DIR to every "
                        "rank): the first rank to compile a program "
                        "publishes the executable, restarted/backing-"
                        "off workers cold-start from disk instead of "
                        "recompiling (sharing is lock-free — "
                        "concurrent ranks race benignly; see "
                        "docs/compile_cache.md)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: restart failed workers this many times")
    p.add_argument("--restart_backoff", type=float, default=1.0,
                   help="base seconds of exponential backoff before a "
                        "restart (doubles per restart; 0 disables)")
    p.add_argument("--restart_backoff_max", type=float, default=30.0,
                   help="backoff ceiling in seconds")
    p.add_argument("--crash_loop_threshold", type=int, default=3,
                   help="abort when this many worker failures land "
                        "within --crash_loop_window seconds (restarting "
                        "a deterministic failure burns restarts for "
                        "nothing); 0 disables")
    p.add_argument("--crash_loop_window", type=float, default=60.0,
                   help="crash-loop detection window in seconds")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="kill + restart a worker whose heartbeat file "
                        "goes stale for this many seconds (distinguishes "
                        "a HUNG worker from a crashed one; 0 disables). "
                        "Workers beat via distributed.init_parallel_env "
                        "or launch.heartbeat.start_heartbeat")
    p.add_argument("--heartbeat_interval", type=float, default=1.0,
                   help="seconds between worker heartbeats (exported as "
                        "PT_HEARTBEAT_INTERVAL)")
    p.add_argument("--elastic", action="store_true",
                   help="when a worker exhausts its restart budget, "
                        "re-render the mesh spec for the surviving world "
                        "size and restart the remaining workers instead "
                        "of aborting (the resized mesh resumes from the "
                        "retained checkpoint via resilience.reshard)")
    p.add_argument("--devices", default=None,
                   help="accepted for reference compat (unused on TPU)")
    p.add_argument("script", help="training script")
    p.add_argument("script_args", nargs="...",
                   help="arguments passed through to the script")
    args = p.parse_args(argv)
    if args.elastic and args.nnodes > 1:
        # each host runs its own supervisor; a per-node downsize would
        # re-render PT_NUM_PROCESSES / rank numbering on this node only,
        # handing jax.distributed.initialize conflicting world specs
        p.error("--elastic requires --nnodes=1: supervisors do not "
                "coordinate a downsize across hosts")
    from ...device import tpu_chips_visible
    if args.nproc_per_node > 1 and tpu_chips_visible():
        # every worker claims the host's TPU at start-up and a chip
        # belongs to one process: the second rank would fail or hang
        p.error("--nproc_per_node > 1 cannot run on a TPU host: one "
                "controller process drives every local chip (workers are "
                "not placed one per chip)")
    return args


def _worker_env(args, local_rank, restarts=0, world=None, hb_path=None):
    """Per-rank environment — the rendered "mesh spec" each worker reads
    (PT_NUM_PROCESSES/PT_PROCESS_ID feed jax.distributed.initialize via
    init_parallel_env).  `world` overrides the spec on an elastic
    downsize: the surviving workers restart seeing the smaller world."""
    env = dict(os.environ)
    nproc = world if world is not None else args.nproc_per_node
    world_total = args.nnodes * nproc
    rank = args.node_rank * nproc + local_rank
    env["PT_COORDINATOR"] = args.master
    env["PT_NUM_PROCESSES"] = str(world_total)
    env["PT_PROCESS_ID"] = str(rank)
    env["PT_LOCAL_RANK"] = str(local_rank)
    # restart ordinal: lets the script know it is a recovery attempt
    # (resilience.manager.restart_count() reads this to e.g. prefer
    # checkpoint fallback over strict resume)
    env["PT_RESTART_COUNT"] = str(restarts)
    if hb_path:
        env["PT_HEARTBEAT_FILE"] = hb_path
        env["PT_HEARTBEAT_INTERVAL"] = str(args.heartbeat_interval)
    if args.cache_dir:
        # every rank shares one executable store; a restart (this very
        # supervisor's backoff path) then skips trace+compile entirely
        env["PADDLE_TPU_CACHE_DIR"] = os.path.abspath(args.cache_dir)
    # reference-compatible aliases user scripts may read
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINERS_NUM"] = str(world_total)
    return env


class _Worker:
    def __init__(self, args, local_rank, hb_dir=None):
        self.args = args
        self.local_rank = local_rank
        self.restarts = 0
        self.restart_at = 0.0   # monotonic deadline of a pending restart
        self.started_at = 0.0
        self._hb_mtime = None   # last observed heartbeat-file mtime
        self._hb_seen_at = 0.0  # monotonic time that mtime was observed
        self.proc = None
        self.log = None
        self.hb_path = (os.path.join(hb_dir, f"hb.{local_rank}")
                        if hb_dir else None)

    def start(self, world=None):
        cmd = [sys.executable, self.args.script] + self.args.script_args
        stdout = stderr = None
        if self.args.log_dir:
            os.makedirs(self.args.log_dir, exist_ok=True)
            rank = self.args.node_rank * self.args.nproc_per_node + \
                self.local_rank
            if self.log:
                self.log.close()
            self.log = open(os.path.join(self.args.log_dir,
                                         f"worker.{rank}.log"), "ab")
            stdout = stderr = self.log
        if self.hb_path and os.path.exists(self.hb_path):
            os.unlink(self.hb_path)   # stale mtime from the last life
        self.proc = subprocess.Popen(
            cmd, env=_worker_env(self.args, self.local_rank,
                                 restarts=self.restarts, world=world,
                                 hb_path=self.hb_path),
            stdout=stdout, stderr=stderr)
        self.started_at = time.monotonic()
        self._hb_mtime = None
        self._hb_seen_at = self.started_at

    def poll(self):
        return self.proc.poll()

    def heartbeat_stale(self, timeout, now):
        """True when this worker is beating but went silent past
        `timeout` — a hang, not a crash (no-file workers never report
        stale: the script may simply not emit heartbeats).  The mtime is
        used only as a change detector; staleness itself is measured on
        the supervisor's monotonic clock, so a wall-clock step (NTP)
        cannot declare the whole fleet hung at once."""
        if not self.hb_path or self.proc is None or \
                self.proc.poll() is not None:
            return False
        try:
            mtime = os.path.getmtime(self.hb_path)
        except OSError:
            return False   # never beat: not participating
        if mtime != self._hb_mtime:   # fresh beat observed
            self._hb_mtime = mtime
            self._hb_seen_at = now
            return False
        return now - self._hb_seen_at > timeout and \
            now - self.started_at > timeout

    def kill(self):
        if self.proc and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()   # reap: the old process must be gone
                                   # before an elastic respawn reuses its
                                   # rank/heartbeat file/coordinator port
        if self.log:
            self.log.close()
            self.log = None


def run(argv=None):
    import tempfile
    from ...resilience.backoff import Backoff, CrashLoopDetector
    args = _parse_args(sys.argv[1:] if argv is None else argv)
    hb_dir = None
    if args.heartbeat_timeout > 0:
        hb_dir = args.log_dir or tempfile.mkdtemp(prefix="pt_launch_hb_")
        os.makedirs(hb_dir, exist_ok=True)
    workers = [_Worker(args, lr, hb_dir=hb_dir)
               for lr in range(args.nproc_per_node)]
    world = None          # None = the spec as parsed; set on downsize
    backoff = Backoff(base=args.restart_backoff,
                      max_delay=args.restart_backoff_max)
    # one detector across all local workers: a deterministic failure
    # takes every rank down in lockstep, and restarting into it again
    # only burns the restart budget
    detector = CrashLoopDetector(threshold=args.crash_loop_threshold,
                                 window=args.crash_loop_window)
    for w in workers:
        w.start(world=world)
    try:
        while True:
            running = False
            now = time.monotonic()
            for w in workers:
                if w.proc is None:       # restart pending its backoff
                    running = True
                    if now >= w.restart_at:
                        w.start(world=world)
                    continue
                if args.heartbeat_timeout > 0 and \
                        w.heartbeat_stale(args.heartbeat_timeout, now):
                    # no exit code but no liveness either: a HANG (wedged
                    # collective), not a crash — kill it ourselves so the
                    # restart path below gets its exit code
                    print(f"[launch] worker {w.local_rank} heartbeat "
                          f"stale > {args.heartbeat_timeout:.1f}s — "
                          f"hung, not crashed; killing for restart",
                          file=sys.stderr)
                    w.kill()
                code = w.poll()
                if code is None:
                    running = True
                elif code != 0:
                    crash_looping = detector.record_failure()
                    if crash_looping:
                        print(f"[launch] worker {w.local_rank} exited "
                              f"{code}: {detector.recent_failures} "
                              f"failures within "
                              f"{args.crash_loop_window:.0f}s — crash "
                              f"loop, aborting instead of restarting",
                              file=sys.stderr)
                        for o in workers:
                            if o is not w:
                                o.terminate()
                        return code
                    if w.restarts < args.max_restarts:
                        w.restarts += 1
                        delay = backoff.delay(w.restarts - 1)
                        print(f"[launch] worker {w.local_rank} exited "
                              f"{code}; restart "
                              f"{w.restarts}/{args.max_restarts} in "
                              f"{delay:.1f}s (PT_RESTART_COUNT="
                              f"{w.restarts})",
                              file=sys.stderr)
                        w.proc = None
                        w.restart_at = now + delay
                        running = True
                    elif args.elastic and len(workers) > 1:
                        # elastic downsize: this rank is gone for good —
                        # re-render the mesh spec for the surviving
                        # world size and restart the survivors into it
                        # (they resume from the retained checkpoint,
                        # resharded by resilience.reshard)
                        workers.remove(w)
                        if w.log:
                            w.log.close()
                            w.log = None
                        world = len(workers)
                        print(f"[launch] worker {w.local_rank} failed "
                              f"with code {code}, restart budget "
                              f"exhausted; elastic downsize — "
                              f"re-rendering mesh spec for world "
                              f"{world} (was {world + 1})",
                              file=sys.stderr)
                        for i, o in enumerate(workers):
                            o.terminate()
                            o.local_rank = i
                            if o.hb_path:
                                o.hb_path = os.path.join(hb_dir,
                                                         f"hb.{i}")
                            o.restarts += 1   # a recovery attempt:
                            o.proc = None     # PT_RESTART_COUNT bumps
                            o.restart_at = now
                        running = True
                        break   # workers mutated: restart the scan
                    else:
                        print(f"[launch] worker {w.local_rank} failed "
                              f"with code {code}; stopping all",
                              file=sys.stderr)
                        for o in workers:
                            if o is not w:
                                o.terminate()
                        return code
            if not running:
                return 0
            time.sleep(0.2)
    except KeyboardInterrupt:
        for w in workers:
            w.terminate()
        return 130
    finally:
        for w in workers:
            if w.log:
                w.log.close()
                w.log = None


def launch():
    sys.exit(run())
