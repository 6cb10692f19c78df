"""The span recorder (paddle_tpu/observability/trace.py): one clock (Unix
nanoseconds, the profiler's), one ring that lets the OLDEST record go
and counts it, one record shape with parent and request identifier."""
import collections
import importlib.util
import os
import sys
import threading
import time

import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability import trace

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools",
                      "trace_check.py")
F = {name: i for i, name in enumerate(trace.FIELDS)}


def _trace_check():
    spec = importlib.util.spec_from_file_location("trace_check", _TOOLS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def recorder():
    trace.clear()
    yield trace
    trace.clear()


@pytest.fixture
def small_ring(recorder, monkeypatch):
    monkeypatch.setattr(trace, "_ring", collections.deque(maxlen=4))
    return recorder


def test_the_clock_is_unix_nanoseconds_and_never_steps_back():
    before = time.time_ns()
    reads = [trace.now_ns() for _ in range(1000)]
    after = time.time_ns()
    assert reads == sorted(reads)
    assert before - 1_000_000 <= reads[0] and reads[-1] <= after + 1_000_000


def test_the_old_epoch_and_the_instant_event_are_gone():
    assert not hasattr(trace, "_EPOCH")
    assert not hasattr(trace, "add_instant")
    assert not hasattr(trace, "_events")


def test_the_ring_is_sized_for_16384_engine_steps_with_their_children():
    assert trace._ring.maxlen == trace.RING >= 16_384 * 12


def test_record_keeps_parent_rid_and_counts(recorder):
    root = recorder.record("root", 5, 30, counts={"rows": 2})
    kid = recorder.record("child", 10, 20, parent=root, rid=7,
                          counts={"tokens": 3, "name": "a count, no field"})
    by_name = {r[F["name"]]: r for r in recorder.spans()}
    child, parent = by_name["child"], by_name["root"]
    assert child[F["parent"]] == root == parent[F["sid"]]
    assert child[F["sid"]] == kid != root
    assert child[F["rid"]] == 7 and parent[F["rid"]] is None
    assert child[F["counts"]] == {"tokens": 3, "name": "a count, no field"}
    assert parent[F["counts"]] == {"rows": 2}
    assert child[F["t1_ns"]] - child[F["t0_ns"]] == 10
    assert len(child) == len(trace.FIELDS)


def test_the_ring_drops_the_oldest_and_counts_it(small_ring):
    for i in range(10):
        small_ring.record(f"s{i}", i, i + 1)
    assert [r[0] for r in small_ring.spans()] == ["s6", "s7", "s8", "s9"]
    assert small_ring.dropped() == 6
    small_ring.clear()
    assert small_ring.dropped() == 0 and small_ring.spans() == []


def test_a_mark_survives_drops(small_ring):
    for i in range(3):
        small_ring.record(f"old{i}", i, i + 1)
    mark = small_ring.mark()
    for i in range(3):
        small_ring.record(f"new{i}", i, i + 1)
    assert small_ring.dropped() == 2
    assert [e["name"] for e in small_ring.events(mark)] == [
        "new0", "new1", "new2"]
    # a mark older than what the ring still holds exports what is left
    assert [e["name"] for e in small_ring.events(0)] == [
        "old2", "new0", "new1", "new2"]


def test_traced_nests_and_knows_its_id_from_the_start(recorder):
    with recorder.traced("outer", rid=3, cat="serving",
                         counts={"n": 1}) as outer:
        with recorder.traced("inner", parent=outer.sid):
            pass
        outer.counts["late"] = 9
    inner, outer_rec = recorder.spans()      # the child ends first
    assert inner[F["name"]] == "inner"
    assert inner[F["parent"]] == outer_rec[F["sid"]] == outer.sid
    assert outer_rec[F["t0_ns"]] <= inner[F["t0_ns"]] \
        <= inner[F["t1_ns"]] <= outer_rec[F["t1_ns"]]
    assert outer_rec[F["counts"]] == {"n": 1, "late": 9}
    assert outer_rec[F["rid"]] == 3 and outer_rec[F["cat"]] == "serving"


def test_traced_records_when_the_block_raises(recorder):
    with pytest.raises(ValueError):
        with recorder.traced("boom"):
            raise ValueError("x")
    assert [r[0] for r in recorder.spans()] == ["boom"]


def test_traced_needs_no_enable_and_span_does(recorder):
    assert not obs.enabled()
    with obs.span("gated"):
        pass
    with recorder.traced("always"):
        pass
    assert [r[0] for r in recorder.spans()] == ["always"]


def test_add_complete_lands_on_the_recorders_clock(recorder):
    t0 = time.perf_counter()
    now = recorder.now_ns()
    recorder.add_complete("older_caller", "step", t0, 0.002,
                          args={"step": 4, "parent": "the caller's own"})
    (rec,) = recorder.spans()
    assert abs(rec[F["t0_ns"]] - now) < 1_000_000
    assert rec[F["t1_ns"]] - rec[F["t0_ns"]] == 2_000_000
    assert rec[F["cat"]] == "step" and rec[F["parent"]] is None
    assert rec[F["counts"]] == {"step": 4, "parent": "the caller's own"}


def test_spans_since(recorder):
    recorder.record("a", 100, 200)
    recorder.record("b", 300, 400)
    assert [r[0] for r in recorder.spans()] == ["a", "b"]
    assert [r[0] for r in recorder.spans(since_ns=200)] == ["a", "b"]
    assert [r[0] for r in recorder.spans(since_ns=201)] == ["b"]
    assert recorder.spans(since_ns=401) == []


def test_chrome_trace_is_valid_and_in_unix_microseconds(recorder):
    with recorder.traced("step", rid=1, cat="serving",
                         counts={"rows": 2}) as sp:
        recorder.record("kid", recorder.now_ns(), recorder.now_ns(),
                        parent=sp.sid)
    doc = recorder.chrome_trace()
    assert _trace_check().check_events(doc, require_cats=("serving",)) == []
    ev = [e for e in doc["traceEvents"] if e["name"] == "step"][0]
    assert abs(ev["ts"] - time.time_ns() / 1e3) < 5e6      # within 5 s
    assert ev["args"]["rid"] == 1 and ev["args"]["rows"] == 2
    kid = [e for e in doc["traceEvents"] if e["name"] == "kid"][0]
    assert kid["args"]["parent"] == ev["args"]["sid"]


def test_many_threads_lose_no_record(recorder):
    """More threads than cores, a short switch interval: every append is
    counted and ids stay unique."""
    n_threads, each = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(each):
                with recorder.traced(f"t{k}", rid=k):
                    pass
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    recs = recorder.spans()
    assert len(recs) == n_threads * each == recorder.mark()
    assert len({r[F["sid"]] for r in recs}) == len(recs)
    assert collections.Counter(r[F["rid"]] for r in recs) == {
        k: each for k in range(n_threads)}
