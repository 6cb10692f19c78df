"""idle_explained_share.* (%): of device 0's idle time in the traced
window, the share that lies inside a span of the program other than
``serving.decode.wait``: the host was doing something the spans name
while the chip stood still.  Idle time inside the wait (the device had
work and the host was only waiting for it) and outside every
``serving.step`` (the harness's loop between two steps) is not
explained.

A LOWER BOUND.  The profiler stamps a session's device events some
milliseconds off its host events, another amount each session (PERF.md,
PR 25), and nothing the harness keeps pins that shift.  The steps bound
it (``program_spans.device_shift``: no decode program starts before its
dispatch began nor ends after its wait returned), and the value is the
LOWEST share that any end of that bracket gives: at least so much of the
idle time has a named phase, wherever in the bracket the truth lies.
With no bracket (no step holds both spans and a program, or no shift
satisfies all of them) the reader gives None.

Prints one line with the idle seconds by span name at that end and the
share at both ends, and one with what the spans cost: the median
``serving.step`` inside the profiler session beside the quiet steps',
with the rows each decoded, and the quiet steps' median wait.  Source:
the program's own spans laid over the device trace."""
import statistics

from benchmark import harness, program_spans, trace
from benchmark.program_spans import COUNTS, NAME, T0, T1


def _p50_ms(spans):
    return statistics.median((r[T1] - r[T0]) / 1e6 for r in spans) \
        if spans else float("nan")


def _rows_p50(steps):
    return statistics.median(
        r[COUNTS].get("decode_rows", 0) for r, _ in steps) \
        if steps else float("nan")


def _share(idle):
    explained = sum(v for k, v in idle.items()
                    if k not in (program_spans.WAIT, "outside"))
    return 100.0 * explained / sum(idle.values())


def read(run):
    got = program_spans.serving(run)
    if got is None or not run["trace"]["devices"]:
        return None
    tr = run["trace"]
    dev = tr["devices"][min(tr["devices"])]
    first = got["first_traced"]
    traced = got["steps"][first:first + got["n_traced"]]
    allowed = program_spans.device_shift(traced, got["offset_ns"],
                                         dev["modules"])
    t0, t1 = trace.window_of(tr)
    gaps = trace.idle_gaps(dev["ops"], t0, t1)
    if allowed is None or not gaps:
        return None
    pieces = program_spans.leaves(traced, got["offset_ns"])
    ends = [(shift, program_spans.idle_by_label(
        [(s + shift, d) for s, d in gaps], pieces)) for shift in allowed]
    shift, idle = min(ends, key=lambda e: _share(e[1]))
    harness.say("idle seconds by program span: " + " ".join(
        f"{k}={v / 1e9:.4f}" for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1]))
        + f"; idle {sum(idle.values()) / 1e9:.4f} of "
        f"{(t1 - t0) / 1e9:.4f} s traced ('outside' is the harness's loop "
        f"between steps); device events shifted by {shift / 1e3:.0f} us, "
        f"the end at which the share reads lowest: the steps allow "
        f"{allowed[0] / 1e3:.0f}..{allowed[1] / 1e3:.0f} us, where it "
        f"reads {_share(ends[0][1]):.1f}..{_share(ends[1][1]):.1f} %; "
        f"clock offset {got['offset_ns']} ns")
    waits = [c for _, kids in got["quiet"] for c in kids
             if c[NAME] == program_spans.WAIT]
    harness.say(
        f"serving.step ms p50: quiet "
        f"{_p50_ms([r for r, _ in got['quiet']]):.4f} "
        f"({_rows_p50(got['quiet']):g} rows) traced "
        f"{_p50_ms([r for r, _ in traced]):.4f} "
        f"({_rows_p50(traced):g} rows); {program_spans.WAIT} ms p50 quiet "
        f"{_p50_ms(waits):.4f}")
    return _share(idle)
