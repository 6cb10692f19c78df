"""`paged_live_block_share.*` on a synthetic run: the two block counts of
the ``serving.step`` roots, summed over the quiet steps alone.  No
profiler, no program."""
import pytest

from benchmark import harness

OFFSET = 1_790_000_000_123_456_789      # profile_start_time, Unix ns
MS = 1_000_000
# (step ms, kv_blocks_live, kv_blocks_walked); the profiler runs over
# the last three steps, the harness timed the four before them
STEPS = [(18.0, 900, 4096), (19.0, 300, 312), (21.5, 310, 321),
         (20.0, 0, 0), (22.5, 290, 303),
         (23.0, 5, 4000), (19.5, 5, 4000), (24.0, 5, 4000)]
N_QUIET, N_TRACED = 4, 3


def build(counted=True):
    recs, hs = [], []
    t = OFFSET - 150 * MS
    for i, (ms, live, walked) in enumerate(STEPS):
        counts = {"decode_rows": 20}
        if counted:
            counts.update(kv_blocks_live=live, kv_blocks_walked=walked)
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [20.0] * N_QUIET,
            "trace": {"devices": {}, "spans": hs}}


@pytest.mark.parametrize("name", ["paged_live_block_share.chat",
                                  "paged_live_block_share.overload"])
def test_share_of_the_quiet_steps_walked_blocks_that_were_live(name):
    read = harness.load_reader(name)
    # steps 1..4: the first step and the traced ones stay out, and a
    # step without decode rows adds nothing
    assert read(build()) == pytest.approx(
        100.0 * (300 + 310 + 0 + 290) / (312 + 321 + 0 + 303))
    # the parent's roots carry decode_rows alone: nothing, not 0
    assert read(build(counted=False)) is None
    assert read(dict(build(), program_spans=None)) is None
    assert read({"program_spans": None}) is None


def test_no_decode_step_in_the_quiet_part_reads_nothing():
    run = build()
    run["program_spans"] = [
        r[:6] + ({"decode_rows": 0, "kv_blocks_live": 0,
                  "kv_blocks_walked": 0},) + r[7:]
        for r in run["program_spans"]]
    assert harness.load_reader("paged_live_block_share.chat")(run) is None
