"""The spans `LLMEngine.step()` and `TrainStep.__call__` write to the
recorder, with no `observability.enable()`: a step's phases nest inside
its root and in order (its own program's `prepare` and `dispatch`, then
`wait`, `fetch` and `sample` of the program the step BEFORE dispatched:
the engine runs one decode program ahead of the host), the root counts
what it dispatched and, one step late, what it landed, every finished
request leaves one `serving.request` with its marks in order, and the
recorder's clock is the profiler's (checked against a real CPU profiler
session)."""
import glob
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics, trace
from paddle_tpu.serving import LLMEngine
from paddle_tpu.text import GPTConfig, GPTForCausalLM

F = {name: i for i, name in enumerate(trace.FIELDS)}
ORDER = ["serving.schedule", "serving.prefill", "serving.schedule",
         "serving.decode.prepare", "serving.decode.dispatch",
         "serving.decode.wait", "serving.decode.fetch", "serving.sample"]
LENS = (5, 21, 3, 9, 30, 7)
NEW = 6


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, tensor_parallel=False))


@pytest.fixture(scope="module")
def served(model):
    """One drained run of six requests (two of them longer than the
    prefill chunk) and what it left in the recorder and the registry."""
    assert not obs.enabled()
    reg = metrics.registry()
    reg.reset()
    trace.clear()
    eng = LLMEngine(model, num_blocks=48, block_size=8, max_running=4,
                    prefill_chunk=16)
    rng = np.random.RandomState(0)
    reqs = [eng.add_request(rng.randint(0, 64, size=n).tolist(),
                            max_new_tokens=NEW) for n in LENS[:4]]
    summaries = [eng.step() for _ in range(3)]
    reqs += [eng.add_request(rng.randint(0, 64, size=n).tolist(),
                             max_new_tokens=NEW) for n in LENS[4:]]
    while eng.has_work:
        summaries.append(eng.step())
    recs = trace.spans()
    hist = reg.histogram("serving_decode_batch")
    out = {"reqs": reqs, "recs": recs, "summaries": summaries,
           "decode_batch": (hist.count, hist.sum),
           "dropped": trace.dropped(),
           "slots": eng.max_running, "table_cols": eng.table_cols}
    assert eng.close() == ([], [])
    trace.clear()
    return out


def _steps(recs):
    roots = [r for r in recs if r[F["name"]] == "serving.step"]
    kids = {}
    for r in recs:
        if r[F["parent"]] is not None:
            kids.setdefault(r[F["parent"]], []).append(r)
    return [(r, sorted(kids.get(r[F["sid"]], []), key=lambda c: c[F["t0_ns"]]))
            for r in sorted(roots, key=lambda r: r[F["t0_ns"]])]


def test_one_root_per_step_and_nothing_dropped(served):
    assert len(_steps(served["recs"])) == len(served["summaries"])
    assert served["dropped"] == 0


def test_children_lie_inside_their_root_and_in_order(served):
    for root, kids in _steps(served["recs"]):
        assert kids, "a step with work has phases"
        cur = root[F["t0_ns"]]
        for c in kids:
            assert cur <= c[F["t0_ns"]] <= c[F["t1_ns"]]
            cur = c[F["t1_ns"]]
        assert cur <= root[F["t1_ns"]]
        assert sum(c[F["t1_ns"]] - c[F["t0_ns"]] for c in kids) \
            <= root[F["t1_ns"]] - root[F["t0_ns"]]
        # the names follow ORDER, prefill any number of times, the
        # decode phases all or none
        names = [c[F["name"]] for c in kids]
        it = iter(ORDER)
        want = next(it)
        for name in names:
            while name != want:
                want = next(it)     # StopIteration = out of order
        # the two halves of the decode lane, each whole or absent: this
        # step's program is built and dispatched; the program of the
        # step BEFORE is waited for, fetched and emitted
        dispatched = [n for n in names if n in ORDER[3:5]]
        landed = [n for n in names if n in ORDER[5:]]
        assert dispatched in ([], ORDER[3:5]) and landed in ([], ORDER[5:])
        assert names.count("serving.schedule") == 2


def test_the_root_counts_the_rows_it_decoded_and_a_prefill_its_chunk(
        served):
    for (root, kids), summary in zip(_steps(served["recs"]),
                                     served["summaries"]):
        counts = root[F["counts"]]
        assert sorted(counts) == ["decode_rows", "kv_blocks_live",
                                  "kv_blocks_walked", "logit_rows_fetched",
                                  "rows_chained", "rows_dropped",
                                  "rows_picked_on_device"]
        # the rows DISPATCHED by this step, of which those whose token
        # was the pick of the program before, taken on the device
        rows = counts["decode_rows"]
        assert rows == summary["decoded"]
        assert counts["rows_chained"] <= rows
        # every request is greedy: the program chose each row's token,
        # and no step copied its logits; the count lands with the picks,
        # on the step that fetched and emitted them.  No request stops
        # early, so no pick is thrown away
        assert counts["rows_picked_on_device"] == summary["emitted"]
        assert counts["logit_rows_fetched"] == counts["rows_dropped"] == 0
        # every row lives in a block or more.  This model's heads are 8
        # wide, so the XLA fallback serves its decode steps, and that
        # gathers every column of every slot's table
        assert rows <= counts["kv_blocks_live"] \
            <= counts["kv_blocks_walked"] \
            <= served["slots"] * served["table_cols"]
        assert counts["kv_blocks_walked"] \
            == (served["slots"] * served["table_cols"] if rows else 0)
        # a prefill counts its chunk's tokens, the context it started
        # at, the blocks (of 8) its queries see and the blocks its
        # program reads (on the CPU the gather: the whole table); the
        # other phases count nothing
        chunks = [c[F["counts"]] for c in kids
                  if c[F["name"]] == "serving.prefill"]
        assert all(sorted(c) == ["ctx", "kv_blocks_live", "kv_blocks_walked",
                                 "tokens"] for c in chunks)
        assert all(c["kv_blocks_live"] == -(-(c["ctx"] + c["tokens"]) // 8)
                   and c["kv_blocks_walked"] == served["table_cols"]
                   for c in chunks)
        assert sum(c["tokens"] for c in chunks) == summary["prefilled"]
        assert all(c[F["counts"]] == {} for c in kids
                   if c[F["name"]] != "serving.prefill")
        assert bool(summary["prefilled"]) == bool(chunks)


def test_a_step_lands_the_program_of_the_step_before(served):
    """The engine runs one decode program ahead of the host: a step
    dispatches its own program FIRST and then waits for, fetches and
    emits the picks of the program the step before dispatched, so the
    counts of what was picked stand one step behind `decode_rows`."""
    steps = _steps(served["recs"])
    rows = [r[F["counts"]]["decode_rows"] for r, _ in steps]
    picked = [r[F["counts"]]["rows_picked_on_device"] for r, _ in steps]
    chained = [r[F["counts"]]["rows_chained"] for r, _ in steps]
    assert picked[0] == 0 and rows[-1] == 0
    assert picked[1:] == rows[:-1]
    assert [s["emitted"] for s in served["summaries"]] == picked
    assert sum(picked) == sum(rows) == NEW * len(LENS)
    # a request's first row is fed by the host, every later one chained
    assert sum(rows) - sum(chained) == len(LENS)
    for (root, kids), before in zip(steps, [0] + rows[:-1]):
        by_name = {c[F["name"]]: c for c in kids}
        # what a step waits for is what the step before dispatched
        assert ("serving.decode.wait" in by_name) == bool(before)
        assert ("serving.decode.dispatch" in by_name) \
            == bool(root[F["counts"]]["decode_rows"])
        if "serving.decode.wait" in by_name \
                and "serving.decode.dispatch" in by_name:
            assert by_name["serving.decode.dispatch"][F["t1_ns"]] \
                <= by_name["serving.decode.wait"][F["t0_ns"]]
    # the last token of the run is delivered by a step that dispatches
    # nothing: `has_work` held until then
    assert served["summaries"][-1]["decoded"] == 0
    assert served["summaries"][-1]["emitted"] > 0


def test_where_the_kernel_serves_the_root_counts_its_ragged_walk(
        monkeypatch):
    """Heads 128 wide under PADDLE_TPU_PALLAS=interpret: the decode
    program runs the pallas kernel, and the root counts the blocks the
    rows live in and one more for each dead slot.  The tokens are the
    fallback's."""
    pt.seed(0)
    wide = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=1,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, tensor_parallel=False))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (5, 19, 9)]

    def run():
        trace.clear()
        eng = LLMEngine(wide, num_blocks=24, block_size=8, max_running=4,
                        prefill_chunk=16)
        reqs = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        eng.run()
        roots = [r[F["counts"]] for r, _ in _steps(trace.spans())]
        trace.clear()
        return [r.generated for r in reqs], roots, eng

    tokens, gathered, eng = run()
    full = eng.max_running * eng.table_cols
    assert {c["kv_blocks_walked"] for c in gathered} <= {0, full}
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got, walked, _ = run()
    assert got == tokens
    decoding = [c for c in walked if c["decode_rows"]]
    assert decoding
    for c in decoding:
        assert c["kv_blocks_walked"] - c["kv_blocks_live"] \
            == eng.max_running - c["decode_rows"]
        assert c["kv_blocks_walked"] < full
    assert [c["kv_blocks_live"] for c in walked] \
        == [c["kv_blocks_live"] for c in gathered]


def test_decode_rows_equal_the_decode_batch_observations(served):
    rows = [r[F["counts"]]["decode_rows"]
            for r, _ in _steps(served["recs"])
            if r[F["counts"]]["decode_rows"]]
    assert (len(rows), sum(rows)) == served["decode_batch"]
    fetched = [c for _, kids in _steps(served["recs"]) for c in kids
               if c[F["name"]] == "serving.decode.fetch"]
    assert len(fetched) == len(rows)


def test_one_request_span_per_finished_request_with_marks_in_order(served):
    spans = {r[F["rid"]]: r for r in served["recs"]
             if r[F["name"]] == "serving.request"}
    assert sorted(spans) == sorted(q.id for q in served["reqs"])
    assert sum(1 for r in served["recs"]
               if r[F["name"]] == "serving.request") == len(LENS)
    for q, n in zip(served["reqs"], LENS):
        rec = spans[q.id]
        m = rec[F["counts"]]
        assert sorted(m) == ["admitted", "first_token", "prefill_done"]
        # the span runs from arrival to finish, the marks lie between
        assert rec[F["t0_ns"]] <= m["admitted"] <= m["prefill_done"] \
            <= m["first_token"] <= rec[F["t1_ns"]]
        assert abs(rec[F["t0_ns"]] - q.arrival_t * 1e9) < 1000
        # the request's marks are on the clock of the steps that served
        # it: admission and first token fall inside a serving.step
        roots = [r for r, _ in _steps(served["recs"])]
        for mark in ("admitted", "first_token"):
            assert any(r[F["t0_ns"]] <= m[mark] <= r[F["t1_ns"]]
                       for r in roots), mark


def test_prefill_spans_carry_their_requests_id(served):
    by_rid = {}
    for r in served["recs"]:
        if r[F["name"]] == "serving.prefill":
            by_rid[r[F["rid"]]] = by_rid.get(r[F["rid"]], 0) + 1
    # one span per request and chunk: a prompt of n tokens prefills
    # n - 1, and a step's budget of 16 tokens is shared, so a request may
    # be cut into more chunks than its own length asks for
    assert sorted(by_rid) == sorted(q.id for q in served["reqs"])
    for q, n in zip(served["reqs"], LENS):
        assert -(-(n - 1) // 16) <= by_rid[q.id] <= n - 1
    assert by_rid[served["reqs"][4].id] >= 2        # 29 tokens, chunk 16
    batch = [r for r in served["recs"]
             if r[F["name"]].startswith("serving.decode")]
    assert batch and all(r[F["rid"]] is None for r in batch)


def test_a_request_cancelled_in_the_queue_leaves_out_the_marks_it_missed(
        model):
    trace.clear()
    eng = LLMEngine(model, num_blocks=48, block_size=8, max_running=4,
                    prefill_chunk=16)
    req = eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.cancel(req)
    (rec,) = [r for r in trace.spans() if r[F["name"]] == "serving.request"]
    assert rec[F["rid"]] == req.id and rec[F["counts"]] == {}
    eng.close()
    trace.clear()


def test_a_preempted_request_is_prefilled_again_under_its_rid(model):
    """A pool too small for three growing requests evicts the youngest:
    it is prefilled again under the same `rid` and still leaves ONE
    `serving.request`, whose `admitted` mark is its first admission."""
    trace.clear()
    eng = LLMEngine(model, num_blocks=7, block_size=4, max_running=3,
                    prefill_chunk=16)
    reqs = [eng.add_request([1 + i, 2, 3, 4, 5, 6, 7], max_new_tokens=10)
            for i in range(3)]
    eng.run()
    recs = trace.spans()
    assert sum(q.preemptions for q in reqs) > 0
    for q in reqs:
        prefills = [r for r in recs if r[F["name"]] == "serving.prefill"
                    and r[F["rid"]] == q.id]
        assert len(prefills) >= 1 + q.preemptions
        (life,) = [r for r in recs if r[F["name"]] == "serving.request"
                   and r[F["rid"]] == q.id]
        assert life[F["counts"]]["admitted"] <= prefills[0][F["t0_ns"]]
    assert eng.close() == ([], [])
    trace.clear()


def test_the_recorders_clock_is_the_profilers(model, tmp_path):
    """A real CPU profiler session around three engine steps: each
    `serving.step` stands in the xplane within 50 us of the recorder's
    time, once `profile_start_time` is added."""
    import jax
    from jax.profiler import ProfileData
    eng = LLMEngine(model, num_blocks=48, block_size=8, max_running=4,
                    prefill_chunk=16)
    eng.add_request([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=8)
    eng.step()                          # compile outside the session
    eng.step()
    trace.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    mine = sorted(r[F["t0_ns"]] for r in trace.spans()
                  if r[F["name"]] == "serving.step")
    eng.close()
    trace.clear()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start = None
    seen = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving."):
                    seen.setdefault(e.name, []).append(e.start_ns)
    assert start is not None
    theirs = sorted(seen["serving.step"])
    assert len(mine) == len(theirs) == 3
    for a, b in zip(mine, theirs):
        assert abs(a - (b + start)) < 50_000
    # the phases stand in the xplane too, under the same names
    assert {"serving.schedule", "serving.decode.dispatch",
            "serving.decode.wait", "serving.decode.fetch",
            "serving.sample"} <= set(seen)


def test_train_call_span_and_its_children():
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    net = nn.Linear(4, 2)
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = pt.to_tensor(np.ones((3, 4), np.float32))
    y = pt.to_tensor(np.zeros((3, 2), np.float32))
    trace.clear()
    for _ in range(3):
        step(x, y)
    recs = trace.spans()
    trace.clear()
    calls = [r for r in recs if r[F["name"]] == "train.call"]
    assert len(calls) == 3
    for call in calls:
        kids = sorted((r for r in recs if r[F["parent"]] == call[F["sid"]]),
                      key=lambda r: r[F["t0_ns"]])
        assert [k[F["name"]] for k in kids] == ["train.call.lookup",
                                                "train.call.dispatch"]
        assert call[F["t0_ns"]] <= kids[0][F["t0_ns"]]
        assert kids[0][F["t1_ns"]] == kids[1][F["t0_ns"]]
        assert kids[1][F["t1_ns"]] <= call[F["t1_ns"]]
