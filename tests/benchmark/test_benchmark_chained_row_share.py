"""`chained_row_share.*` on a synthetic run: the rows whose token the
decode program took from the program before it, on the device, over the
rows dispatched, summed over the quiet steps alone.  No profiler, no
program."""
import pytest

from benchmark import harness

OFFSET = 1_790_000_000_123_456_789      # profile_start_time, Unix ns
MS = 1_000_000
# (step ms, decode_rows, rows_chained); the profiler runs over the last
# three steps, the harness timed the four before them
STEPS = [(18.0, 32, 0), (19.0, 30, 29), (21.5, 32, 30), (20.0, 0, 0),
         (22.5, 18, 0), (23.0, 32, 0), (19.5, 32, 32), (24.0, 32, 32)]
N_QUIET, N_TRACED = 4, 3
NAMES = ["chained_row_share.chat", "chained_row_share.overload",
         "chained_row_share.doc"]


def build(counted=True):
    recs, hs = [], []
    t = OFFSET - 150 * MS
    for i, (ms, rows, chained) in enumerate(STEPS):
        counts = {"decode_rows": rows, "rows_picked_on_device": rows,
                  "logit_rows_fetched": 0}
        if counted:
            counts.update(rows_chained=chained, rows_dropped=0)
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [20.0] * N_QUIET,
            "trace": {"devices": {}, "spans": hs}}


@pytest.mark.parametrize("name", NAMES)
def test_share_of_the_quiet_steps_rows_that_chained_on_the_device(name):
    read = harness.load_reader(name)
    # steps 1..4: the first step and the traced ones stay out; a step
    # that waited for its own program (18 rows, none chained) counts its
    # rows below the line alone
    assert read(build()) == pytest.approx(100.0 * (29 + 30) / (30 + 32 + 18))
    # a program that runs no step ahead carries no such count: nothing,
    # not 0
    assert read(build(counted=False)) is None
    assert read(dict(build(), program_spans=None)) is None
    assert read({"program_spans": None}) is None


def test_no_dispatch_in_the_quiet_part_reads_nothing():
    run = build()
    run["program_spans"] = [
        r[:6] + ({"decode_rows": 0, "rows_chained": 0, "rows_dropped": 0},)
        + r[7:] for r in run["program_spans"]]
    assert harness.load_reader("chained_row_share.chat")(run) is None


def test_the_three_entries_are_in_the_benchmark():
    """Found by the first one's name; nothing is asserted about what a
    later PR appends behind them."""
    bench = harness._json(harness.os.path.join(harness.ROOT,
                                               "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAMES[0])
    tail = bench["per_layer"][at:at + 3]
    assert [(m["name"], m["moves"], m["workloads"]) for m in tail] == [
        (NAMES[0], "tpot_p95_ms", ["gpt3-1.3b.chat"]),
        (NAMES[1], "serve_tokens_per_s", ["gpt3-1.3b.chat-overload"]),
        (NAMES[2], "serve_tokens_per_s", ["kimi-vl-a3b.doc-overload"])]
    for m in tail:
        assert (m["layer"], m["source"], m["better"], m["unit"]) == (
            "serving engine", "program_span", "higher", "%")
