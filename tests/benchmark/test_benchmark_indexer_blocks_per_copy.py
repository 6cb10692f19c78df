"""`indexer_blocks_per_copy.*` on a synthetic run: the walked blocks
over the indexer walk's DMAs of the ``serving.step`` roots, summed over
the quiet steps alone.  No profiler, no program."""
import pytest

from benchmark import harness

OFFSET = 1_790_000_000_123_456_789      # profile_start_time, Unix ns
MS = 1_000_000
NAME = "indexer_blocks_per_copy.longctx"
# (step ms, kv_blocks_walked, ik_copies); the profiler runs over the
# last three steps, the harness timed the four before them
STEPS = [(18.0, 9000, 9000), (19.0, 1600, 400), (21.5, 1700, 410),
         (20.0, 0, 0), (22.5, 1500, 300),
         (23.0, 4000, 4000), (19.5, 4000, 4000), (24.0, 4000, 4000)]
N_QUIET, N_TRACED = 4, 3


def build(counted=True):
    recs, hs = [], []
    t = OFFSET - 150 * MS
    for i, (ms, walked, copies) in enumerate(STEPS):
        counts = {"decode_rows": 12, "kv_blocks_walked": walked}
        if counted:
            counts["ik_copies"] = copies
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [20.0] * N_QUIET,
            "trace": {"devices": {}, "spans": hs}}


def test_blocks_a_copy_over_the_quiet_steps():
    # steps 1..4: the first step and the traced ones stay out, and a
    # step without decode rows adds nothing
    assert harness.load_reader(NAME)(build()) == pytest.approx(
        (1600 + 1700 + 0 + 1500) / (400 + 410 + 0 + 300))


@pytest.mark.parametrize("run", [
    lambda: build(counted=False),       # the parent's roots: no count
    lambda: dict(build(), program_spans=None),
    lambda: {"program_spans": None}], ids=["uncounted", "no-spans",
                                           "nothing"])
def test_without_the_count_it_reads_nothing(run):
    assert harness.load_reader(NAME)(run()) is None
