"""The program's own spans (``paddle_tpu.observability.trace``), read
for the per-layer metrics that time the inside of ``LLMEngine.step()``
and ``TrainStep.__call__``.

A record is a plain tuple whose first seven fields are (name, start ns,
end ns, identifier, parent, request identifier, counts), on the Unix
clock.  The device trace in ``run["trace"]`` counts from the profiler
session's start, and the harness keeps no ``profile_start_time``, so
`offset_ns` finds that constant from the spans themselves: the program's
``serving.step`` spans are one to one with the harness's ``engine.step``
spans around the same calls.

Where `records` gives None, every reader returns None and its metric is
left out of the line.
"""
import bisect
import statistics

NAME, T0, T1, SID, PARENT, RID, COUNTS = range(7)
STEP, WAIT = "serving.step", "serving.decode.wait"
DISPATCH = "serving.decode.dispatch"
AGREE_NS = 100e3        # two steps' offsets may differ by this much


def records(run):
    """The recorder's records, oldest first, or None.  A test hands them
    in as ``run["program_spans"]``.  The driver also lays this file over
    the parent's checkout, whose recorder has no `spans()`: None there,
    and every reader leaves its metric out."""
    if "program_spans" in run:
        return run["program_spans"]
    from paddle_tpu.observability import trace as recorder
    read = getattr(recorder, "spans", None)
    return read() if read else None


def steps_of(recs):
    """[(root, [children by start])] of the ``serving.step`` spans, in
    the order the steps ran."""
    kids = {}
    for r in recs:
        if r[PARENT] is not None:
            kids.setdefault(r[PARENT], []).append(r)
    roots = sorted((r for r in recs if r[NAME] == STEP),
                   key=lambda r: r[T0])
    return [(r, sorted(kids.get(r[SID], []), key=lambda c: c[T0]))
            for r in roots]


def offset_ns(harness_steps, roots):
    """(offset, index of the first traced root) such that root time -
    offset = trace time, or None.

    `harness_steps` are the ``engine.step`` spans of the trace as
    (start, duration) and `roots` the program's (start, end) in step
    order.  An alignment pairs the k-th harness span with root j0 + k;
    each pair estimates the offset as the distance between the two
    spans' midpoints (the harness's span wraps the program's by
    microseconds).  The alignment holds when no two of its estimates lie
    more than `AGREE_NS` apart; exactly one alignment must hold, else
    None: never a guess."""
    hs = sorted(harness_steps)
    if not hs or len(roots) < len(hs):
        return None
    # whole nanoseconds from the first root on: a float holds Unix
    # nanoseconds only to 256 ns
    base = roots[0][0]
    h_mid = [s + d / 2 for s, d in hs]
    r_mid = [(a - base + b - base) / 2 for a, b in roots]
    found = []
    for j0 in range(len(roots) - len(hs) + 1):
        lo = hi = r_mid[j0] - h_mid[0]
        for k in range(1, len(hs)):
            est = r_mid[j0 + k] - h_mid[k]
            lo, hi = min(lo, est), max(hi, est)
            if hi - lo > AGREE_NS:
                break
        else:
            found.append((base + round(statistics.median(
                r_mid[j0 + k] - h_mid[k] for k in range(len(hs)))), j0))
    return found[0] if len(found) == 1 else None


def serving(run):
    """{"records", "steps", "first_traced", "n_traced", "offset_ns",
    "quiet"} of a traced serving run, or None where the program has no
    spans or no alignment holds.  `quiet` are the ``len(run["step_ms"])`` steps that
    ended before the first traced one: the harness timed the same steps
    with the profiler off."""
    recs, tr = records(run), run.get("trace")
    if not recs or not tr:
        return None
    steps = steps_of(recs)
    hs = [(s, d) for name, s, d in tr["spans"] if name == "engine.step"]
    found = offset_ns(hs, [(r[T0], r[T1]) for r, _ in steps])
    if found is None:
        return None
    offset, first = found
    n_quiet = len(run.get("step_ms") or [])
    if n_quiet > first:
        return None
    return {"steps": steps, "first_traced": first, "n_traced": len(hs),
            "offset_ns": offset, "quiet": steps[first - n_quiet:first],
            "records": recs}


def phase_ms_p50(run, names):
    """Median over the quiet steps of the summed duration (ms) of a
    step's children whose name is in `names`; None without spans."""
    got = serving(run)
    if got is None or not got["quiet"]:
        return None
    return statistics.median(
        sum(c[T1] - c[T0] for c in kids if c[NAME] in names) / 1e6
        for _, kids in got["quiet"])


def leaves(steps, offset):
    """The traced steps as non-overlapping (start, end, label) pieces on
    the trace's clock: each child under its name, the rest of a step
    under ``serving.step`` (its own time)."""
    out = []
    for root, kids in steps:
        cur = root[T0]
        for c in kids:
            if c[T0] > cur:
                out.append((cur - offset, c[T0] - offset, STEP))
            out.append((c[T0] - offset, c[T1] - offset, c[NAME]))
            cur = max(cur, c[T1])
        if root[T1] > cur:
            out.append((cur - offset, root[T1] - offset, STEP))
    return out


def device_shift(steps, offset, modules):
    """(lo, hi) ns: the shifts of the device's events against the host's
    that the steps allow.  The profiler stamps a session's device events
    on a clock that is some milliseconds off its host events' (PERF.md,
    PR 25); but a decode program (the last ``XLA Modules`` event that
    overlaps its step's wait) cannot start before the host began to
    dispatch it, nor end after the wait for it returned.  None where no
    step has both spans and a program, or no shift satisfies all."""
    mods = sorted((s, s + d) for _, s, d in modules)
    lo = hi = None
    for _, kids in steps:
        by_name = {c[NAME]: c for c in kids}
        if DISPATCH not in by_name or WAIT not in by_name:
            continue
        begun = by_name[DISPATCH][T0] - offset
        w0, w1 = by_name[WAIT][T0] - offset, by_name[WAIT][T1] - offset
        over = [m for m in mods if m[0] < w1 and m[1] > w0]
        if not over:
            continue
        start, end = over[-1]
        lo = begun - start if lo is None else max(lo, begun - start)
        hi = w1 - end if hi is None else min(hi, w1 - end)
    return (lo, hi) if lo is not None and lo <= hi else None


def idle_by_label(gaps, pieces):
    """{label: idle ns} of the `gaps` [(start, dur)] that fall inside
    each piece; what falls inside none stands under ``outside``."""
    starts = [p[0] for p in pieces]
    out = {}
    for s, d in gaps:
        e, inside = s + d, 0.0
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(pieces) and pieces[i][0] < e:
            a, b, label = pieces[i]
            cut = min(b, e) - max(a, s)
            if cut > 0:
                out[label] = out.get(label, 0.0) + cut
                inside += cut
            i += 1
        if d > inside:
            out["outside"] = out.get("outside", 0.0) + d - inside
    return out
