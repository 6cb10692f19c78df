"""The readers of the program's own spans on a synthetic run: device
operations and harness spans on the profiler session's clock, program
spans on the Unix clock a known offset away.  No profiler, no program."""
import json
import os

import pytest

from benchmark import harness, program_spans

OFFSET = 1_790_000_000_123_456_789      # profile_start_time, Unix ns
MS = 1_000_000
# (schedule, prefill, schedule, prepare, dispatch, wait, fetch, sample)
# per step, in ms; no two steps alike, as no two real ones are
PHASES = [(0.10 + 0.01 * i, 0.5 * (i % 3 == 0), 0.05, 0.30, 0.60,
           14.0 + 0.4 * (i % 5), 1.50 + 0.02 * i, 1.00 + 0.03 * i)
          for i in range(12)]
NAMES = ("serving.schedule", "serving.prefill", "serving.schedule",
         "serving.decode.prepare", "serving.decode.dispatch",
         "serving.decode.wait", "serving.decode.fetch", "serving.sample")
OWN = 20_000            # a step's own time after its last child, ns
LOOP = 200_000          # the harness's loop between two steps, ns
LAG = 200_000           # dispatch begins -> program starts; ends -> wait returns
SKEW = 1_500_000        # the trace stamps device events this much early
FIRST_TRACED, N_TRACED, N_QUIET = 7, 4, 5


def rec(name, t0, t1, sid=None, parent=None, rid=None, **counts):
    return (name, int(t0), int(t1), sid, parent, rid, counts, "serving", 1)


def build():
    """(program records, harness spans, device ops) of twelve steps; the
    profiler runs over steps 7..10.  A decode program starts `LAG` after
    its dispatch begins and ends `LAG` before its wait returns, and the
    trace stamps it `SKEW` early."""
    recs, hs, ops = [], [], []
    t = OFFSET - 100 * MS
    for i, phases in enumerate(PHASES):
        sid, t0 = 1000 + i, t
        for name, ms in zip(NAMES, phases):
            if ms == 0:
                continue
            d = round(ms * MS)      # whole ns: t is too large for a float
            recs.append(rec(name, t, t + d, parent=sid,
                            rid=77 if name == "serving.prefill" else None))
            if name == "serving.decode.dispatch":
                started = t + LAG
            if name == "serving.decode.wait" \
                    and FIRST_TRACED <= i < FIRST_TRACED + N_TRACED:
                ops.append(("fusion.1", float(started - SKEW - OFFSET),
                            float(t + d - LAG - started)))
            t += d
        t += OWN
        recs.append(rec("serving.step", t0, t, sid=sid, decode_rows=20))
        if FIRST_TRACED <= i < FIRST_TRACED + N_TRACED:
            hs.append(("engine.step", float(t0 - 3_000 - OFFSET),
                       float(t - t0 + 7_000)))
        t += LOOP
    return recs, hs, ops


@pytest.fixture
def run():
    recs, hs, ops = build()
    roots = [r for r in recs if r[0] == "serving.step"]
    opened = roots[FIRST_TRACED - N_QUIET][1]
    cut = roots[FIRST_TRACED][1]
    # a request's span runs from its arrival to its finish; the marks in
    # between are its counts (prefill done 2 ms before the first token)
    for k, (arrival, admitted, first) in enumerate([
            (opened + 1, opened + 5 * MS, opened + 25 * MS),     # 20 ms
            (opened + 2, opened + 6 * MS, opened + 46 * MS),     # 40 ms
            (opened - 9, opened + 1 * MS, opened + 9 * MS),      # too early
            (opened + 3, opened + 7 * MS, cut + 1)]):            # too late
        recs.append(rec("serving.request", arrival, first + MS, rid=k,
                        admitted=admitted, prefill_done=first - 2 * MS,
                        first_token=first))
    recs.append(rec("serving.request", opened + 4, opened + 5, rid=9))
    return {"program_spans": recs, "step_ms": [20.0] * N_QUIET,
            "trace": {"devices": {0: {"ops": ops, "modules": [
                ("jit_pure(1)", s0, d) for _, s0, d in ops]}},
                "spans": hs + [("arrivals", 0.0, 10.0)]}}


def quiet_median(column):
    vals = sorted(column(p) for p in
                  PHASES[FIRST_TRACED - N_QUIET:FIRST_TRACED])
    return vals[len(vals) // 2]


def read(name, run):
    return harness.load_reader(name)(dict(run, metric=name))


def test_the_offset_is_found_to_microseconds(run):
    got = program_spans.serving(run)
    assert got["first_traced"] == FIRST_TRACED
    assert got["n_traced"] == N_TRACED and len(got["quiet"]) == N_QUIET
    assert abs(got["offset_ns"] - OFFSET) <= 2_000
    assert isinstance(got["offset_ns"], int)


def test_offset_search_alone():
    roots = [(OFFSET + a * MS, OFFSET + b * MS) for a, b in
             [(0, 100), (300, 450), (700, 790), (1000, 1200), (1500, 1530)]]
    # each harness span wraps its root by 2 us before and 4 us after
    hs = [(700.0 * MS - 2_000, 90.0 * MS + 6_000),
          (1000.0 * MS - 2_000, 200.0 * MS + 6_000)]
    assert program_spans.offset_ns(hs, roots) == (OFFSET - 1_000, 2)
    assert program_spans.offset_ns([], roots) is None
    assert program_spans.offset_ns(hs * 3, roots) is None   # more than roots


def test_no_alignment_gives_none_never_a_guess(run):
    bent = dict(run, trace=dict(run["trace"]))
    spans = list(run["trace"]["spans"])
    name, s, d = spans[2]
    spans[2] = (name, s + 150_000, d)       # one step 150 us off the rest
    bent["trace"]["spans"] = spans
    assert program_spans.serving(bent) is None
    assert read("step_fetch_ms_p50.chat", bent) is None
    assert read("idle_explained_share.chat", bent) is None


def test_two_alignments_give_none():
    """Steps that repeat exactly fit the harness's spans at every shift:
    the search does not pick one."""
    roots = [(OFFSET + i * MS, OFFSET + i * MS + 500_000) for i in range(8)]
    hs = [(float(i * MS), 500_000.0) for i in range(3)]
    assert program_spans.offset_ns(hs, roots) is None


def test_fewer_program_steps_than_quiet_steps_give_none(run):
    assert program_spans.serving(dict(run, step_ms=[20.0] * 8)) is None


@pytest.mark.parametrize("name,column", [
    ("step_schedule_ms_p50.chat", lambda p: p[0] + p[2]),
    ("step_schedule_ms_p50.overload", lambda p: p[0] + p[2]),
    ("step_dispatch_ms_p50.chat", lambda p: p[1] + p[3] + p[4]),
    ("step_dispatch_ms_p50.overload", lambda p: p[1] + p[3] + p[4]),
    ("step_fetch_ms_p50.chat", lambda p: p[6]),
    ("step_fetch_ms_p50.overload", lambda p: p[6]),
    ("step_sample_ms_p50.chat", lambda p: p[7]),
    ("step_sample_ms_p50.overload", lambda p: p[7]),
])
def test_phase_medians_over_the_quiet_steps(run, name, column):
    assert read(name, run) == pytest.approx(quiet_median(column), abs=1e-6)


def test_idle_is_laid_to_the_span_that_covers_it(run, capsys):
    share = read("idle_explained_share.chat", run)
    traced = PHASES[FIRST_TRACED:FIRST_TRACED + N_TRACED]
    # the window runs from the first traced program's start to the last
    # one's end: three rounds of the wait's tail, fetch, sample, own
    # time, loop, schedule .. prepare.  The device's clock is only
    # bounded by the steps, and the reader takes the end of the bracket
    # at which the share reads lowest: a program that starts the moment
    # its dispatch begins, so that both lags lie inside the wait
    after = sum(p[6] + p[7] for p in traced[:-1]) * MS + 3 * OWN
    before = sum(p[0] + p[1] + p[2] + p[3] for p in traced[1:]) * MS
    wait, loop = 3 * 2 * LAG, 3 * LOOP
    want = 100.0 * (after + before) / (after + before + wait + loop)
    assert share == pytest.approx(want, abs=0.05)
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines()
            if ln.startswith("# bench: idle seconds by program span")][0]
    assert f"outside={loop / 1e9:.4f}" in line
    assert f"serving.decode.wait={wait / 1e9:.4f}" in line
    assert "serving.decode.fetch=" in line and "serving.sample=" in line
    assert "shifted by 1300 us, the end at which the share reads lowest: " \
        "the steps allow 1300..1700 us" in line
    # at the other end both lags lie inside the dispatch: higher
    high = 100.0 * (after + before + wait) / (after + before + wait + loop)
    assert f"where it reads {want:.1f}..{high:.1f} %" in line
    cost = [ln for ln in out.splitlines() if "serving.step ms p50" in ln][0]
    assert "quiet" in cost and "(20 rows) traced" in cost


def test_the_device_clock_is_bounded_by_dispatch_and_wait(run):
    got = program_spans.serving(run)
    traced = got["steps"][FIRST_TRACED:FIRST_TRACED + N_TRACED]
    mods = run["trace"]["devices"][0]["modules"]
    lo, hi = program_spans.device_shift(traced, got["offset_ns"], mods)
    assert lo == pytest.approx(SKEW - LAG, abs=2_000)
    assert hi == pytest.approx(SKEW + LAG, abs=2_000)
    assert program_spans.device_shift(traced, got["offset_ns"], []) is None
    # a program stamped as ending 0.5 ms later than the others leaves no
    # shift that fits every step: nothing is read, nothing guessed
    name, s0, d = mods[1]
    late = [mods[0], (name, s0, d + 500_000)] + mods[2:]
    assert program_spans.device_shift(traced, got["offset_ns"], late) is None
    bent = dict(run, trace=dict(run["trace"], devices={
        0: dict(run["trace"]["devices"][0], modules=late)}))
    assert read("idle_explained_share.chat", bent) is None


def test_idle_by_label_cuts_a_gap_across_pieces():
    pieces = [(0, 10, "a"), (10, 30, "b"), (50, 60, "a")]
    assert program_spans.idle_by_label([(5, 50)], pieces) == {
        "a": 10.0, "b": 20.0, "outside": 20.0}
    assert program_spans.idle_by_label([(100, 5)], pieces) == {"outside": 5}
    assert program_spans.idle_by_label([], pieces) == {}


def test_admit_to_first_token_takes_the_requests_of_the_quiet_part(
        run, capsys):
    # of five requests two arrived in the quiet part and had their first
    # token before the profiler started: 20 and 40 ms
    assert read("admit_to_first_token_p95_ms.chat", run) \
        == pytest.approx(20 + 0.95 * 20)
    assert "admitted -> prefill_done ms p95 37.0000, prefill_done -> " \
        "first_token ms p95 2.0000 over 2 requests" \
        in capsys.readouterr().out


def test_train_host_is_the_median_call_before_the_traced_steps(capsys):
    recs = []
    for sid, d in enumerate((90, 3, 4, 5, 6, 50, 60)):
        recs += [rec("train.call.lookup", 0, MS, parent=sid),
                 rec("train.call.dispatch", MS, (d - 1) * MS, parent=sid),
                 rec("train.call", 0, d * MS, sid=sid)]
    run = {"program_spans": recs, "traced_steps": 2}
    assert read("train_host_ms_p50", run) == pytest.approx(5.0)
    assert "train.call ms p50 5.0000: train.call.lookup=1.0000 " \
        "train.call.dispatch=3.0000" in capsys.readouterr().out
    assert read("train_host_ms_p50", {"program_spans": recs[:2]}) is None


@pytest.mark.parametrize("name,want", [("flash_fwd_ms_per_step", 1.5),
                                       ("flash_bwd_ms_per_step", 3.5)])
def test_flash_kernel_times_by_the_kernels_own_names(name, want):
    ops = [("mosaic:flash_attention_fwd.3", 0.0, 2.0 * MS),
           ("mosaic:flash_attention_fwd.7", 0.0, 1.0 * MS),
           ("mosaic:flash_attention_bwd_dkv.4", 0.0, 4.0 * MS),
           ("mosaic:flash_attention_bwd_dq.5", 0.0, 3.0 * MS),
           ("fusion.9", 0.0, 50.0 * MS)]
    run = {"trace": {"devices": {0: {"ops": ops, "modules": []}},
                     "spans": []}, "traced_steps": 2}
    assert read(name, run) == pytest.approx(want)
    unnamed = {"trace": {"devices": {0: {"ops": [
        ("mosaic:jvp__.3", 0.0, 2.0 * MS)], "modules": []}}, "spans": []},
        "traced_steps": 2}
    assert read(name, unnamed) is None          # the parent's names: never 0
    assert read(name, {"trace": None}) is None


NEW = ["step_schedule_ms_p50", "step_dispatch_ms_p50", "step_fetch_ms_p50",
       "step_sample_ms_p50", "idle_explained_share"]


@pytest.mark.parametrize("name", [
    s + x for s in NEW for x in (".chat", ".overload")]
    + ["admit_to_first_token_p95_ms.chat", "train_host_ms_p50"])
def test_a_program_without_the_recorder_reads_nothing(run, name):
    """The parent of the PR that brought the spans: every reader returns
    None and raises nothing, so the line leaves its metric out."""
    assert read(name, dict(run, program_spans=None)) is None
    assert read(name, {"program_spans": None}) is None


def test_records_come_from_the_programs_recorder(monkeypatch):
    from paddle_tpu.observability import trace as recorder
    recorder.clear()
    recorder.record("serving.step", 1, 2)
    assert [r[0] for r in program_spans.records({})] == ["serving.step"]
    recorder.clear()
    monkeypatch.delattr(recorder, "spans")
    assert program_spans.records({}) is None


def test_the_fourteen_entries_are_in_the_benchmark():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    spans = [s + x for s in NEW for x in (".chat", ".overload")] \
        + ["admit_to_first_token_p95_ms.chat", "train_host_ms_p50"]
    for name in spans:
        assert by_name[name]["source"] == "program_span"
        assert len(by_name[name]["workloads"]) == 1
    for name in ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step"):
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["workloads"] == ["gpt3-1.3b.train"]
    # the fourteen stand together and in order; what a later PR appends
    # after them is its own
    fourteen = spans + ["flash_fwd_ms_per_step", "flash_bwd_ms_per_step"]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(fourteen[0])
    assert len(fourteen) == 14 and names[at:at + 14] == fourteen
