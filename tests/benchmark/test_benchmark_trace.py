"""The trace reduction on a small synthetic trace: events are plain
(name, start_ns, dur_ns) tuples, so no profiler is needed."""
import pytest

from benchmark import harness, trace

OPS = [("fusion.1", 0, 100), ("flash_fwd", 50, 100), ("all-reduce.3", 300, 50),
       ("fusion.1", 400, 100), ("paged_decode_attention", 600, 200),
       ("all-gather-start.1", 900, 50)]
SPANS = [("engine.step", 0, 160), ("arrivals", 160, 120),
         ("train.step", 240, 20), ("engine.step", 500, 480)]


def test_busy_is_the_union_not_the_sum():
    assert trace.busy_ns(OPS) == 150 + 50 + 100 + 200 + 50


@pytest.mark.parametrize("events,want", [
    ([], 0.0), ([("a", 5, 10)], 10.0),
    ([("a", 0, 10), ("b", 10, 10)], 20.0),
    ([("a", 0, 100), ("b", 10, 10)], 100.0),
    ([("b", 10, 10), ("a", 0, 5)], 15.0),
])
def test_busy_cases(events, want):
    assert trace.busy_ns(events) == want


def test_idle_gaps_cover_the_rest_of_the_window():
    gaps = trace.idle_gaps(OPS, 0, 1000)
    assert gaps == [(150, 150), (350, 50), (500, 100), (800, 100),
                    (950, 50)]
    assert sum(d for _, d in gaps) + trace.busy_ns(OPS) == 1000


def test_idle_gap_before_first_and_after_last_event():
    assert trace.idle_gaps([("a", 10, 10)], 0, 40) == [(0, 10), (20, 20)]


@pytest.mark.parametrize("pattern,want", [
    (r"flash", 100), (r"paged_decode", 200), (r"fusion", 200),
    (r"all-reduce|all-gather", 100), (r"nothing", 0)])
def test_named_sums(pattern, want):
    assert trace.named_sum_ns(OPS, pattern) == want


def test_clip_cuts_events_to_the_window():
    assert trace.clip(OPS, 60, 320) == [
        ("fusion.1", 60, 40), ("flash_fwd", 60, 90), ("all-reduce.3", 300, 20)]


def test_top_ops_sums_by_name():
    top = trace.top_ops(OPS, 2)
    assert top[0] == ["fusion.1", 200 / 1e9]
    assert top[1] == ["paged_decode_attention", 200 / 1e9]
    assert len(trace.top_ops(OPS, 10)) == 5


def test_gaps_are_labelled_by_the_innermost_covering_span():
    gaps = trace.idle_gaps(OPS, 0, 1000)
    labelled = dict((round(s * 1e9), n) for n, s in
                    trace.label_gaps(gaps, SPANS, 10))
    assert trace.label_gaps(gaps, SPANS, 1) == [["arrivals", 150 / 1e9]]
    assert trace.label_gaps([(240, 10)], SPANS) == [["train.step", 1e-8]]
    assert trace.label_gaps([(490, 5)], SPANS) == [["uncovered", 5e-9]]
    assert 150 in labelled


def test_window_and_reduce_over_two_devices():
    tr = {"devices": {0: {"ops": OPS, "modules": []},
                      1: {"ops": [("fusion.1", 0, 500)], "modules": []}},
          "spans": SPANS}
    assert trace.window_of(tr) == (0, 950)
    extra, breakdown = harness.reduce_trace(tr, 2)
    assert extra["window_s"] == pytest.approx(950e-9)
    assert extra["busy_s"] == pytest.approx((550 + 500) / 2 * 1e-9)
    assert breakdown["device_ops"][0][0] in ("fusion.1",
                                             "paged_decode_attention")
    assert len(breakdown["idle_gaps"]) == 4
    one, _ = harness.reduce_trace(tr, 1)
    assert one["busy_s"] == pytest.approx(550e-9)


@pytest.mark.parametrize("gap, phase", [
    ((110, 30), "serving.decode.dispatch"), ((160, 30), "serving.decode.wait"),
    ((262, 6), "serving.decode.fetch"), ((282, 8), "serving.step"),
    ((296, 3), "engine.step")])
def test_a_serving_gap_is_labelled_by_the_programs_phase(gap, phase):
    """The program's phases lie inside its `serving.step`, which lies
    inside the harness's `engine.step`; the runtime's events, kept for
    the device clock, lie inside a phase and name no gap."""
    spans = [("engine.step", 95, 205), ("serving.step", 100, 195),
             ("serving.decode.dispatch", 100, 50),
             ("serving.decode.wait", 150, 100),
             ("serving.decode.fetch", 250, 30),
             ("tpu::System::Execute=>IssueSequencedEvent", 120, 10),
             ("ReadSyncFlag", 170, 5)]
    assert {s[0] for s in spans} <= set(harness.SPANS
                                        + harness.RUNTIME_EVENTS)
    ops = [("fusion.1", 0, gap[0]), ("fusion.2", gap[0] + gap[1], 400)]
    tr = {"devices": {0: {"ops": ops, "modules": []}}, "spans": spans}
    _, breakdown = harness.reduce_trace(tr, 1)
    assert breakdown["idle_gaps"] == [[phase, gap[1] / 1e9]]


def test_a_trace_without_device_events_is_an_error():
    with pytest.raises(ValueError):
        trace.window_of({"devices": {0: {"ops": [], "modules": []}}})


def _run(name, **kw):
    tr = {"devices": {0: {"ops": OPS, "modules": [
        ("jit_step", 0, 400), ("jit_step", 400, 500), ("jit_step", 900, 600),
        ("jit_small", 0, 1)]}}, "spans": []}
    return harness.load_reader(name)(dict(trace=tr, **kw))


def test_idle_share_reader():
    assert _run("device_idle_share.train") == pytest.approx(
        100 * (1 - 550 / 950))


def test_step_median_reader_takes_the_heaviest_module():
    assert _run("train_step_ms_p50") == pytest.approx(500 / 1e6)


@pytest.mark.parametrize("name", ["device_idle_share.chat", "flash_roofline",
                                  "paged_roofline.chat", "train_step_ms_p50"])
def test_readers_return_nothing_without_a_trace(name):
    assert harness.load_reader(name)({"trace": None}) is None


def test_roofline_readers_never_return_zero_for_no_match():
    tr = {"devices": {0: {"ops": [("fusion.1", 0, 10)], "modules": []}},
          "spans": []}
    common = dict(trace=tr, traced_steps=2, traced_context_sum=100,
                  chips=1, peaks={"bf16_flops": 1e12,
                                  "hbm_bytes_per_s": 1e11},
                  config={"hidden_size": 8, "num_hidden_layers": 1,
                          "num_attention_heads": 2,
                          "intermediate_size": 16, "vocab_size": 10},
                  mix={"batch": 1, "seq": 4})
    assert harness.load_reader("flash_roofline")(common) is None
    assert harness.load_reader("paged_roofline.chat")(common) is None
