#!/usr/bin/env python
"""serve — a driver over paddle_tpu.serving (engine or router mode).

Builds a model, feeds it requests, streams tokens as they decode, and
prints the serving metrics snapshot when the queue drains.  Requests
are lines of space-separated token ids on stdin (one request per line),
or ``--random N`` synthetic prompts.

    # 6 random prompts through a tiny GPT, streaming
    python tools/serve.py --random 6

    # a real preset, AOT warm start from a prior --export-aot run
    python tools/serve.py --preset gpt3-125M --load-aot /tmp/aot < ids.txt

    # the serving tier: 2 replicas behind the router (least-loaded
    # admission, heartbeat health, failover re-prefill, load shedding)
    python tools/serve.py --random 12 --replicas 2

    # the same tier with REAL fault isolation: one worker PROCESS per
    # replica over the framed socket transport — a segfault/OOM in one
    # replica is an exit code, not a tier outage
    python tools/serve.py --random 12 --replicas 2 --proc

``--export-aot DIR`` writes the replica's per-bucket AOT artifacts
(serving.aot) after the run, so the next replica starts zero-compile;
in router mode ``--load-aot`` warm-starts every replica AND every
respawned replacement.  Watermark/deadline knobs (``--shed-queue-depth``,
``--shed-free-blocks``, ``--queue-deadline``, ``--ttl``) arm the
admission-control story from docs/serving.md.

**Graceful shutdown**: SIGTERM (or SIGINT) follows the
CheckpointManager preemption-flush pattern — the handler only records
the signal; the drive loop then stops admitting, drains in-flight
requests (finish, or expire past ``--drain-ttl``), flushes a final
metrics snapshot to stderr, and frees the pool(s).  In ``--proc`` mode
the worker serving counters are pulled over the ``metrics_snapshot``
RPC and merged, then termination is forwarded to every worker process
group and reaped (TERM→KILL) before the snapshot prints — a kill that
lands while a worker is still compiling leaves no orphans.
"""
import argparse
import json
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", default=None,
                    help="GPTConfig preset (default: a tiny demo config)")
    ap.add_argument("--random", type=int, default=0, metavar="N",
                    help="serve N random prompts instead of stdin")
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--do-sample", action="store_true")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--num-blocks", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-running", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="N>1 serves through the multi-replica Router "
                         "(in-process replicas unless --proc)")
    ap.add_argument("--proc", action="store_true",
                    help="router mode with PROCESS-per-replica "
                         "workers: each replica is a spawned "
                         "`paddle_tpu.serving.worker` process behind "
                         "the framed socket transport — a crash/OOM "
                         "in one replica cannot take the tier down")
    ap.add_argument("--spawn-grace", type=float, default=120.0,
                    help="--proc: heartbeat grace (s) before a fresh "
                         "worker's FIRST beat (covers import+compile)")
    ap.add_argument("--heartbeat-timeout", type=float, default=5.0,
                    help="router: stale-beat seconds before a replica "
                         "is evicted as hung")
    ap.add_argument("--shed-queue-depth", type=int, default=None,
                    help="admission watermark: shed when this many "
                         "requests are already queued")
    ap.add_argument("--shed-free-blocks", type=int, default=None,
                    help="admission watermark: shed when free blocks "
                         "drop below this with a backlog queued")
    ap.add_argument("--queue-deadline", type=float, default=None,
                    help="per-request max queue wait (s) before clean "
                         "expiry")
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request total lifetime (s) before clean "
                         "expiry")
    ap.add_argument("--drain-ttl", type=float, default=30.0,
                    help="graceful-shutdown budget (s) for in-flight "
                         "requests after SIGTERM")
    ap.add_argument("--export-aot", metavar="DIR", default=None,
                    help="write per-bucket AOT artifacts after the run")
    ap.add_argument("--load-aot", metavar="DIR", default=None,
                    help="warm-start from exported AOT artifacts")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="no per-token streaming output")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    # graceful shutdown: install the RECORDING handler before the heavy
    # imports/compiles, so a SIGTERM during startup still drains instead
    # of hard-killing (the CheckpointManager preemption-flush pattern —
    # the handler only records; the drive loop does the work)
    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum

    prev = {s: signal.signal(s, _on_signal)
            for s in (signal.SIGTERM, signal.SIGINT)}

    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.jit import compile_cache
    from paddle_tpu.observability import metrics
    from paddle_tpu.text import GPTConfig, GPTForCausalLM

    compile_cache.place_jax_cache()     # workers inherit the directory
    tiny_kw = dict(vocab_size=256, hidden_size=64, num_layers=2,
                   num_heads=4, max_position_embeddings=256,
                   hidden_dropout=0.0, attention_dropout=0.0,
                   tensor_parallel=False)
    preset_kw = dict(hidden_dropout=0.0, attention_dropout=0.0,
                     tensor_parallel=False)
    if args.preset:
        cfg = GPTConfig.from_preset(args.preset, **preset_kw)
    else:
        cfg = GPTConfig(**tiny_kw)
    engine_kw = dict(num_blocks=args.num_blocks,
                     block_size=args.block_size,
                     max_running=args.max_running,
                     prefill_chunk=args.prefill_chunk,
                     shed_queue_depth=args.shed_queue_depth,
                     shed_free_blocks=args.shed_free_blocks)

    warm_start = None
    if args.load_aot:
        def warm_start(eng):
            keys = serving.load_serving_artifacts(eng, args.load_aot)
            print(f"# AOT warm start: loaded {len(keys)} program(s)",
                  file=sys.stderr)

    router = None
    if args.proc:
        # process-per-replica tier: no model, no seed, no backend in
        # THIS process — a chip belongs to one process, and it is the
        # worker's.  Each worker re-derives the model from the spec
        # (seed 0 + the config) and warm-starts itself from --load-aot;
        # respawns do the same
        from paddle_tpu.serving import worker as sw
        sw.check_proc_replicas(args.replicas)
        spec = sw.gpt_spec(preset=args.preset or None,
                           overrides=preset_kw if args.preset else None,
                           config=None if args.preset else tiny_kw,
                           seed=0, engine=engine_kw,
                           load_aot=args.load_aot, lazy=True)

        def replica_factory(name, hb_path, respawning=False):
            return sw.ProcReplica(spec, name, hb_path)

        backend = router = serving.Router(
            None, replicas=args.replicas,
            heartbeat_timeout=args.heartbeat_timeout,
            spawn_grace_s=args.spawn_grace,
            replica_factory=replica_factory)
        # wait for the workers (import+build+AOT) in interruptible
        # slices: a SIGTERM during worker compile must fall through to
        # the drain/close path below, which reaps the whole tier
        while stop["sig"] is None and not router.wait_ready(timeout=0.5):
            pass
    else:
        pt.seed(0)
        with pt.LazyGuard():
            model = GPTForCausalLM(cfg)

        def engine_factory():
            return serving.LLMEngine(model, **engine_kw)

        if args.replicas > 1:
            backend = router = serving.Router(
                engine_factory, replicas=args.replicas,
                heartbeat_timeout=args.heartbeat_timeout,
                warm_start=warm_start)
        else:
            backend = engine_factory()
            if warm_start is not None:
                warm_start(backend)

    if args.random:
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, cfg.vocab_size,
                              size=rs.randint(4, 32)).tolist()
                   for _ in range(args.random)]
    else:
        prompts = [[int(t) for t in line.split()]
                   for line in sys.stdin if line.strip()]
    if not prompts:
        print("no prompts (stdin empty and --random not given)",
              file=sys.stderr)
        return 2

    def on_token(req, tok):
        if not args.quiet:
            print(f"req{req.id} +{tok}", flush=True)

    def on_finish(req):
        toks = req.emitted if router is not None else req.generated
        print(f"req{req.id} DONE ({req.finish_reason}): "
              f"{' '.join(map(str, toks))}", flush=True)

    kw = dict(max_new_tokens=args.max_new_tokens,
              do_sample=args.do_sample, temperature=args.temperature,
              top_k=args.top_k, top_p=args.top_p, on_token=on_token,
              on_finish=on_finish, queue_deadline_s=args.queue_deadline,
              ttl_s=args.ttl)
    shed = 0
    try:
        for p in prompts:
            if stop["sig"] is not None:
                break                # stop admitting the moment we're told
            try:
                if router is not None:
                    router.submit(p, eos_token_id=args.eos, **kw)
                else:
                    backend.add_request(p, eos_token_id=args.eos, **kw)
            except serving.ShedRequest as e:
                shed += 1
                print(f"req SHED ({e.reason}): {e.detail}", flush=True)
        steps = 0
        while backend.has_work and stop["sig"] is None:
            backend.step()
            steps += 1

        if stop["sig"] is not None:
            print(f"# signal {stop['sig']}: draining in-flight requests "
                  f"(budget {args.drain_ttl:g}s)", file=sys.stderr)
            backend.drain(ttl_s=args.drain_ttl)

        if args.export_aot:
            if router is not None:
                print("# --export-aot ignored in router mode (export "
                      "from a single-engine run, then --load-aot the "
                      "tier)", file=sys.stderr)
            else:
                serving.export_serving_artifacts(
                    backend, args.export_aot,
                    prompt_lens=[len(p) for p in prompts])
                print(f"# AOT artifacts exported to {args.export_aot}",
                      file=sys.stderr)
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        # final metrics snapshot BEFORE freeing the pool(s): in --proc
        # mode the serving_* counters live in the WORKER processes, so
        # pull them over the metrics_snapshot RPC while the workers are
        # still alive and merge; close() then forwards termination to
        # every worker process group and REAPS (TERM->KILL escalation,
        # even mid-compile) before the snapshot is printed — no orphans
        reg = metrics.registry()
        snap = {m["name"]: m.get("value", m.get("count"))
                for m in reg.snapshot()
                if m["name"].startswith(("serving_", "router_"))}
        if args.proc and router is not None:
            for _name, recs in router.metrics_snapshot().items():
                for m in recs:
                    key = m["name"]
                    snap[key] = (snap.get(key) or 0) + \
                        (m.get("value", m.get("count")) or 0)
        leaks = backend.close()
        print(json.dumps({
            "requests": len(prompts), "shed": shed,
            "drained": stop["sig"] is not None,
            "leaks": (leaks if router is not None
                      else {"r0": leaks}), "metrics": snap,
        }, indent=1, default=str), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
