"""`prefill_live_block_share.*` on a synthetic run: the two block counts
of the ``serving.prefill`` spans under the quiet steps' roots.  And the
count behind them: `latent_blocks_read` follows the latent prefill
kernel's walk where the kernel serves a chunk, the whole table where the
XLA gather does.  No profiler, no program."""
import pytest

from benchmark import harness

OFFSET = 1_790_000_000_123_456_789      # profile_start_time, Unix ns
MS = 1_000_000
# (step ms, [(kv_blocks_live, kv_blocks_walked) of a chunk]); the
# profiler runs over the last three steps, the harness timed the four
# before them
STEPS = [(18.0, [(900, 1024)]), (19.0, [(300, 312)]),
         (21.5, [(310, 321), (40, 64)]), (20.0, []), (22.5, [(290, 303)]),
         (23.0, [(5, 1024)]), (19.5, []), (24.0, [(5, 1024)])]
N_QUIET, N_TRACED = 4, 3


def build(counted=True):
    recs, hs = [], []
    t = OFFSET - 150 * MS
    sid = 1000
    for i, (ms, chunks) in enumerate(STEPS):
        end = t + round(ms * MS)
        root = sid
        recs.append(("serving.step", t, end, root, None, None,
                     {"decode_rows": 20}, "serving", 1))
        for k, (live, walked) in enumerate(chunks):
            sid += 1
            counts = {"tokens": 512, "ctx": 4096}
            if counted:
                counts.update(kv_blocks_live=live, kv_blocks_walked=walked)
            recs.append(("serving.prefill", t + (k + 1) * MS,
                         t + (k + 2) * MS, sid, root, 7 + k, counts,
                         "serving", 1))
        sid += 1
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [20.0] * N_QUIET,
            "trace": {"devices": {}, "spans": hs}}


def test_share_of_the_quiet_chunks_walked_blocks_that_were_live():
    read = harness.load_reader("prefill_live_block_share.doc")
    # steps 1..4: the first step and the traced ones stay out, a step
    # without a chunk adds nothing, a step with two adds both
    assert read(build()) == pytest.approx(
        100.0 * (300 + 310 + 40 + 290) / (312 + 321 + 64 + 303))
    # a program whose chunk spans carry no counts: nothing, not 0
    assert read(build(counted=False)) is None
    assert read(dict(build(), program_spans=None)) is None
    assert read({"program_spans": None}) is None


def test_no_chunk_in_the_quiet_part_reads_nothing():
    run = build()
    run["program_spans"] = [r for r in run["program_spans"]
                            if r[0] != "serving.prefill"]
    assert harness.load_reader("prefill_live_block_share.doc")(run) is None


@pytest.mark.parametrize("pallas", [None, "interpret"],
                         ids=["xla-gather", "kernel"])
def test_latent_blocks_read_follows_the_path_that_serves(monkeypatch,
                                                        pallas):
    """A bucket of 1,024 rows of 16 heads over rows of 640 in blocks of
    16, a table of 1,024 columns: the kernel reads the blocks up to the
    one that holds its last row's position (never past the table), the
    XLA gather every column; a decode step's dead slot one block."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import pool_blocks_read
    if pallas:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    planes = {"kv": (33000, 16, 640)}
    read = dict(op="latent_paged_attention", table_cols=1024,
                plane_shapes=planes, heads=16, dtype=jnp.bfloat16)
    chunk = [4500 + 1024, 15000 + 1024, 1024]
    walked = pool_blocks_read(lens=chunk, rows=3, queries=1024, **read)
    step = pool_blocks_read(lens=[4501, 1], rows=2, **read)
    if pallas:
        assert walked == 346 + 1002 + 64
        assert step == 282 + 1
    else:
        assert walked == 3 * 1024
        assert step == 2 * 1024
