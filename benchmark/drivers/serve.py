"""Serving cells: ``serving.LLMEngine`` driven through ``add_request`` and
``step`` by an open-loop arrival schedule from the seed.

TTFT counts from the instant a request was DUE, not from when the loop
got round to submitting it.  A lead-in of the same traffic runs before the
window so that it opens on a loaded engine.  ``follow_to_end`` mixes
follow the window's requests to their last token after it closes, under
the lead-out that the schedule goes on offering; the others (overload)
end with the window.
"""
import time

import numpy as np

from benchmark import check, harness, stats, traffic


class Record:
    """The harness's own times of one request (seconds from the window's
    opening)."""
    __slots__ = ("due", "submitted", "admitted", "first", "last", "req",
                 "prompt_len", "refused")

    def __init__(self, due, prompt_len):
        self.due, self.prompt_len = due, prompt_len
        self.submitted = self.admitted = self.first = self.last = None
        self.req, self.refused = None, False


class ServeCell:
    def __init__(self, spec, seed):
        import paddle_tpu as pt
        from paddle_tpu import serving
        from paddle_tpu.observability import metrics
        self.cfg, self.mix = spec["config"], spec["mix"]
        self.seed = seed
        self.ref = harness.load_reference(self.cfg["reference"])
        eng = self.mix["engine"]
        self.positions = int(self.cfg["max_position_embeddings"])
        longest = (int(self.mix["prompt_tokens"]["max"])
                   + int(self.mix["output_tokens"]["max"]))
        if longest > self.positions:
            raise harness.BenchError(
                f"the mix's longest request ({longest} tokens) does not "
                f"fit the configuration's {self.positions} positions")
        pt.seed(0)
        model = harness.build_model(self.cfg)
        model = pt.amp.decorate(models=model, dtype="bfloat16")
        harness.load_weights(model, self.ref, self.cfg, seed)
        self.registry = metrics.registry()
        self.registry.reset()
        self.eng = serving.LLMEngine(
            model, num_blocks=int(eng["num_blocks"]),
            block_size=int(eng["block_size"]),
            max_running=int(eng["max_running"]),
            prefill_chunk=int(eng["prefill_chunk"]), dtype="bfloat16")
        self.model = model
        self.emit = []              # (time, context attended) per token

    def warm(self):
        """Every program shape the engine owns: decode and each prefill
        bucket up to the chunk, one short request apiece."""
        rng = np.random.default_rng(0)
        vocab = int(self.cfg["vocab_size"])
        for key in self.eng.program_keys():
            if key[0] != "prefill":
                continue
            n = min(int(key[1]) + 1, self.positions - 2)
            self.eng.add_request(rng.integers(0, vocab, n),
                                 max_new_tokens=2)
            self.eng.run()

    def counters(self):
        h = self.registry.histogram("serving_decode_batch")
        return {"decode_batch_sum": h.sum, "decode_batch_count": h.count}

    def submit(self, rec, item, now):
        def on_token(req, tok, rec=rec):
            t = time.perf_counter()
            if rec.first is None:
                rec.first = t
            rec.last = t
            self.emit.append((t, req.ctx))
        try:
            rec.req = self.eng.add_request(
                item["prompt"], max_new_tokens=item["max_new_tokens"],
                on_token=on_token)
        except RuntimeError as e:       # ShedRequest, PoolExhausted
            harness.say(f"request refused: {e}")
            rec.refused = True
        rec.submitted = now

    def cancel_all(self):
        sched = self.eng.scheduler
        for req in list(sched.running) + list(sched.waiting):
            self.eng.cancel(req)

    def free(self):
        import gc
        import jax
        self.cancel_all()
        leaks = self.eng.close()
        self.eng = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()
        return leaks


def drive(cell, schedule, seconds, follow, on_tick=None, patience=60.0,
          t_open=None):
    """The arrival loop.  Returns (records, steps, t_open, t_close) with
    every time on time.perf_counter(); `steps` holds (start, duration,
    decoded, prefilled, pool blocks in use, seconds this thread was on
    the CPU) per engine step."""
    from paddle_tpu.serving.scheduler import WAITING
    eng = cell.eng
    if t_open is None:
        lead = -min([r["due"] for r in schedule] + [0.0])
        t_open = time.perf_counter() + lead
    t_close = t_open + seconds
    records = [Record(t_open + r["due"], len(r["prompt"])) for r in schedule]
    in_window = [r for r in records if t_open <= r.due < t_close]
    settled = lambda r: r.refused or r.req.finish_reason is not None
    steps, waiting, i, n = [], [], 0, len(schedule)
    while True:
        now = time.perf_counter()
        with harness.span("arrivals"):
            while i < n and records[i].due <= now:
                cell.submit(records[i], schedule[i], now)
                if not records[i].refused:
                    waiting.append(records[i])
                i += 1
        if on_tick is not None:
            on_tick(now)
        if now >= t_close and (not follow or now >= t_close + patience
                               or all(settled(r) for r in in_window)):
            break
        if eng.has_work:
            cpu = time.thread_time()
            with harness.span("engine.step"):
                st = eng.step()
            steps.append((now, time.perf_counter() - now, st["decoded"],
                          st["prefilled"], eng.pool.used_blocks,
                          time.thread_time() - cpu))
            still = []
            for rec in waiting:
                if rec.req.state == WAITING:
                    still.append(rec)
                else:
                    rec.admitted = now
            waiting = still
        elif i < n:
            with harness.span("idle.wait"):
                time.sleep(max(0.0, min(0.001, records[i].due - now)))
        else:
            break
    return records, steps, t_open, t_close


def depth_by_thirds(depth, t_open, t_close):
    """Mean of the `depth` readings [(time, requests waiting)] in each
    third of the window: a queue that grows third to third is above the
    knee, one that stays under a request is below it."""
    third = (t_close - t_open) / 3
    return [float(np.mean([d for t, d in depth
                           if t_open + k * third <= t
                           < t_open + (k + 1) * third] or [0]))
            for k in range(3)]


def _latencies(records, t_open, t_close, t_end):
    """(ttft ms, tpot ms, queue wait ms, finished, failed) over ALL the
    requests due in the window.  A request that failed or never produced
    a token misses every limit: it carries the time to the run's end."""
    ttft, tpot, wait, finished, failed = [], [], [], 0, 0
    for r in records:
        if not (t_open <= r.due < t_close):
            continue
        reason = None if r.req is None else r.req.finish_reason
        ok = reason == "length"
        finished += ok
        failed += (not ok) and (r.refused or reason is not None)
        first = r.first if r.first is not None else t_end
        ttft.append((first - r.due) * 1e3)
        n = 0 if r.req is None else len(r.req.generated)
        if ok and n > 1:
            tpot.append((r.last - r.first) / (n - 1) * 1e3)
        elif not ok and reason is not None:
            tpot.append((t_end - first) * 1e3)
        if r.admitted is not None:
            wait.append((r.admitted - r.due) * 1e3)
    return ttft, tpot, wait, finished, failed


def sample(records, seed, n_sample):
    """A seeded sample of the finished requests, the longest first."""
    done = [r for r in records
            if r.req is not None and r.req.finish_reason == "length"]
    if not done:
        return []
    size = lambda r: r.prompt_len + len(r.req.generated)
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % 2 ** 63, 4])
    return [longest] + [rest[j] for j in rng.permutation(len(rest))
                        [:max(0, n_sample - 1)]]


def compare(ref, cfg, positions, seed, picked, control=None):
    """(widest gap, tokens compared): the widest gap by which a served
    token's reference logit lies below the reference's best, over every
    served token of the `picked` requests -- one full reference forward
    per request over its prompt and served tokens.  With `control` (a
    lower precision of the reference), the gap read at each of the same
    positions is that of the token the CONTROL puts first."""
    import jax.numpy as jnp
    if not picked:
        return float("inf"), 0
    weights = ref.init_weights(cfg, positions, seed)
    heads = int(cfg["num_attention_heads"])
    widest, compared = 0.0, 0
    for r in picked:
        gen = list(r.req.generated)
        feed = list(r.req.prompt) + gen[:-1]
        tokens = np.zeros((1, positions), np.int32)
        tokens[0, :len(feed)] = feed
        tokens = jnp.asarray(tokens)
        lo = r.prompt_len - 1
        chosen = np.zeros(positions, np.int32)
        chosen[lo:lo + len(gen)] = gen
        chosen = jnp.asarray(chosen)
        if control is not None:
            chosen = ref.next_token_gaps(weights, tokens, chosen, heads,
                                         control)[2].astype(jnp.int32)
        best, took, _ = ref.next_token_gaps(weights, tokens, chosen, heads,
                                            "float32")
        sl = slice(lo, lo + len(gen))
        widest = max(widest, check.widest_logit_gap(
            np.asarray(best)[sl], np.asarray(took)[sl]))
        compared += len(gen)
    return widest, compared


def run(spec, args, t_start, device):
    cfg, mix = spec["config"], spec["mix"]
    vocab = int(cfg["vocab_size"])
    follow = bool(mix.get("follow_to_end", False))
    cell = args.build(spec, args.seed) if getattr(args, "build", None) \
        else ServeCell(spec, args.seed)
    harness.say(f"built at {time.perf_counter() - t_start:.1f} s")
    cell.warm()
    harness.say(f"warm at {time.perf_counter() - t_start:.1f} s")
    schedule = traffic.serve_schedule(mix, args.seed, args.seconds, vocab)
    lead = float(mix.get("lead_s", 0.0))
    trace_s = float(mix.get("trace_seconds", 3.0)) if args.trace else 0.0
    prof = harness.Profiler(spec["name"]) if args.trace else None
    compiles = harness.CompileCounter()
    state = {"open": None, "trace_from": None, "closed": False,
             "c_open": None, "c_cut": None}
    t_plan_open = time.perf_counter() + lead
    depth = []                  # (time, requests waiting) per loop turn

    def on_tick(now):
        depth.append((now, cell.eng.scheduler.queue_depth))
        if state["open"] is None and now >= t_plan_open:
            state["open"] = now
            state["c_open"] = cell.counters()
            compiles.open()
        if prof and state["trace_from"] is None \
                and now >= t_plan_open + args.seconds - trace_s:
            state["c_cut"] = cell.counters()
            prof.start()
            state["trace_from"] = time.perf_counter()
        if not state["closed"] and now >= t_plan_open + args.seconds:
            state["closed"] = True
            if prof:
                prof.stop()
                state["trace_to"] = now

    records, steps, t_open, t_close = drive(
        cell, schedule, args.seconds, follow, on_tick, t_open=t_plan_open)
    t_end = time.perf_counter()
    if not state["closed"]:
        on_tick(max(t_end, t_close))
    n_compiles = compiles.close()
    setup_s = t_open - t_start
    peak = harness.memory_peak_bytes(1) if not args.rehearse else 0
    # ------------------------------------------------------- end to end
    ttft, tpot, wait, finished, failed = _latencies(
        records, t_open, t_close, t_end)
    due_in = sum(1 for r in records if t_open <= r.due < t_close)
    emitted = [t for t, _ in cell.emit]
    tok_rate = stats.rate_over_window(emitted, t_open, t_close)
    late = [(r.submitted - r.due) * 1e3 for r in records
            if r.submitted is not None]
    unfinished = due_in - finished - failed
    harness.say(f"window {t_close - t_open:.1f} s: {due_in} requests due, "
                f"{finished} finished, {failed} failed, {unfinished} "
                f"unfinished at the end; {len(steps)} engine steps; "
                f"tokens/s {tok_rate:.1f}; compilations inside: "
                f"{n_compiles}")
    used = [s[4] for s in steps if t_open <= s[0] < t_close] or [0]
    pool = {"blocks": int(mix["engine"]["num_blocks"]),
            "in_use_mean": float(np.mean(used)), "in_use_peak": int(max(used))}
    harness.say(f"pool blocks in use over the window: mean "
                f"{pool['in_use_mean']:.0f} peak {pool['in_use_peak']} of "
                f"{pool['blocks']} (the rest of the pool is reserve)")
    thirds = depth_by_thirds(depth, t_open, t_close)
    closing = [s[2] for s in steps if s[0] < t_close][-8:]
    harness.say("mean queue depth by third of the window: "
                + " / ".join(f"{d:.1f}" for d in thirds)
                + f"; rows running at the close {max(closing or [0])}")
    slow = max(steps, key=lambda s: s[1])
    harness.say(f"arrival generator lateness ms: p95 "
                f"{stats.percentile(late, 95):.2f} max {max(late):.2f}; "
                f"longest engine step {slow[1] * 1e3:.0f} ms at "
                f"{slow[0] - t_open:.1f} s, this thread on the CPU for "
                f"{slow[5] * 1e3:.0f} ms of it (a stall shows here: on the "
                f"CPU it is the process's own work, off it the thread "
                f"waited or was descheduled)")
    harness.say(f"ttft ms mean {float(np.mean(ttft)):.2f} p50 "
                f"{stats.percentile(ttft, 50):.1f} p95 "
                f"{stats.percentile(ttft, 95):.1f}; tpot ms p50 "
                f"{stats.percentile(tpot, 50):.2f} p95 "
                f"{stats.percentile(tpot, 95):.2f}; queue wait ms p95 "
                f"{stats.percentile(wait, 95):.1f}")
    # BENCHMARK.json's end_to_end entries pick what a cell reports of
    # these; no cell reports ttft_p95_ms today (PERF.md section 2)
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": tok_rate,
           "ttft_p95_ms": stats.percentile(ttft, 95),
           "tpot_p95_ms": stats.percentile(tpot, 95)}
    if follow:
        attempted = due_in
    else:           # the run ends with the window: the settled ones count
        attempted = finished + failed
    # -------------------------------------------------------- per layer
    run_data, breakdown, dev_extra = {}, None, {}
    if args.trace:
        cut = state["trace_from"]
        c0, c1 = state["c_open"], state["c_cut"]
        quiet = [s for s in steps if t_open <= s[0] < cut]
        run_data = {
            "quiet_s": cut - t_open,
            "step_ms": [s[1] * 1e3 for s in quiet],
            "tokens_processed": sum(s[2] + s[3] for s in quiet),
            "decode_batch_sum": c1["decode_batch_sum"]
            - c0["decode_batch_sum"],
            "decode_batch_count": c1["decode_batch_count"]
            - c0["decode_batch_count"],
            "ttft_ms": [(r.first - r.due) * 1e3 for r in records
                        if r.first is not None
                        and t_open <= r.due and r.first < cut],
            "queue_wait_ms": [(r.admitted - r.due) * 1e3 for r in records
                              if r.admitted is not None
                              and t_open <= r.due and r.admitted < cut],
            "traced_context_sum": sum(
                c for t, c in cell.emit
                if cut <= t < state["trace_to"]),
        }
    in_window = [r for r in records if t_open <= r.due < t_close]
    positions = cell.positions
    leaks = cell.free()
    if args.trace:
        tr = prof.read()
        harness.say(f"profiler: start {prof.start_s:.2f} s, stop "
                    f"{prof.stop_s:.2f} s")
        if tr["devices"]:
            dev_extra, breakdown = harness.reduce_trace(tr, 1)
        run_data["trace"] = tr
    # ----------------------------- the comparison, program state freed
    t = time.perf_counter()
    ref = harness.load_reference(cfg["reference"])
    picked = sample(in_window, args.seed, int(mix.get("check_requests", 6)))
    gap, compared = compare(ref, cfg, positions, args.seed, picked)
    harness.say(f"reference: {time.perf_counter() - t:.1f} s over "
                f"{compared} served tokens; pool leaks {leaks}")
    checks = [("served_logit_gap", gap, mix["limits"]["served_logit_gap"]),
              ("failed_requests", float(failed), 0.0),
              ("compiles_in_window", float(n_compiles), 0.0)]
    if follow:
        checks.append(("unfinished_requests", float(unfinished), 0.0))
    return {"e2e": e2e, "attempted": attempted, "failed": failed,
            "checks": checks, "peak": peak, "run": run_data,
            "device_extra": dev_extra, "breakdown": breakdown,
            "extra": {"pool": pool, "queue_depth_by_third": thirds}}


def sweep(spec, args, device):
    """Builder's tool: the same mix at several arrival rates in ONE
    process and set-up, to find the highest rate at which the queue does
    not grow over the window.  Prints one line per rate, no result."""
    cfg, mix = spec["config"], dict(spec["mix"])
    cell = ServeCell(spec, args.seed)
    cell.warm()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate)
        schedule = traffic.serve_schedule(mix, args.seed, args.seconds,
                                          int(cfg["vocab_size"]))
        depth = []
        cell.emit.clear()
        records, steps, t_open, t_close = drive(
            cell, schedule, args.seconds, False,
            lambda now: depth.append(
                (now, cell.eng.scheduler.queue_depth)))
        t_end = time.perf_counter()
        ttft, tpot, wait, finished, failed = _latencies(
            records, t_open, t_close, t_end)
        thirds = depth_by_thirds(depth, t_open, t_close)
        rate_tok = stats.rate_over_window([t for t, _ in cell.emit],
                                          t_open, t_close)
        print(f"sweep rate {rate:.2f}/s: due "
              f"{sum(1 for r in records if r.due >= t_open)} finished "
              f"{finished} failed {failed}; mean queue depth by third "
              + " ".join(f"{d:.1f}" for d in thirds)
              + f"; running at close {len(cell.eng.scheduler.running)}; "
              f"tokens/s {rate_tok:.1f}; ttft p50 "
              f"{stats.percentile(ttft, 50):.0f} p95 "
              f"{stats.percentile(ttft, 95):.0f} ms; tpot p50 "
              f"{stats.percentile(tpot, 50):.1f} p95 "
              f"{stats.percentile(tpot, 95):.1f} ms; step ms p50 "
              f"{stats.percentile([s[1] * 1e3 for s in steps], 50):.1f}"
              f"; pool blocks peak {max(s[4] for s in steps)}",
              flush=True)
        cell.cancel_all()
    cell.free()
    return 0
