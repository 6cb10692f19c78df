"""Continuous-batching serving engine (paddle_tpu/serving).

The load-bearing property: engine output under CONCURRENT interleaved
requests is token-identical to sequential `generate()` per request —
paged attention over gathered pool blocks runs the exact dense-cache
sdpa math, so batching/chunking/preemption may never change a token.
Plus: block-pool alloc/free/refcount invariants, preemption-and-resume
mid-decode, pallas-vs-fallback paged attention equivalence, AOT
round-trip, and the chaos overload drill (tier-1 wiring of
``chaos_check --serving``).
"""
import io
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.serving import (BlockPool, LLMEngine, PoolExhausted,
                                export_serving_artifacts,
                                load_serving_artifacts)
from paddle_tpu.text import (GPTConfig, GPTForCausalLM, LlamaConfig,
                             LlamaForCausalLM)
from paddle_tpu.text.generation import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_gpt():
    pt.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    return GPTForCausalLM(cfg)


@pytest.fixture(scope="module")
def gpt():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def gpt_engine(gpt):
    """One shared engine (its compiled programs amortize across tests;
    every test drains its requests, so state resets between them)."""
    return LLMEngine(gpt, num_blocks=48, block_size=8, max_running=9,
                     prefill_chunk=16)


def _tiny_llama():
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=64, tensor_parallel=False)
    return LlamaForCausalLM(cfg)


def _seq_ref(model, prompt, n, eos=None):
    out = generate(model, pt.to_tensor(np.asarray([prompt], "int64")),
                   max_new_tokens=n, eos_token_id=eos)
    return out.numpy()[0, len(prompt):].tolist()


# ===================================================================
# token parity under concurrent interleaved load (the acceptance bar:
# >= 8 concurrent requests of mixed prompt lengths)
# ===================================================================
def test_engine_parity_concurrent_interleaved(gpt, gpt_engine):
    m, eng = gpt, gpt_engine
    rng = np.random.RandomState(0)
    lens = (5, 11, 3, 9, 14, 7, 4, 12, 6)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in lens]
    refs = [_seq_ref(m, p, 7) for p in prompts]

    # interleave arrivals with decoding: the first wave is mid-flight
    # when the rest join the batch
    reqs = [eng.add_request(p, max_new_tokens=7) for p in prompts[:5]]
    for _ in range(3):
        eng.step()
    reqs += [eng.add_request(p, max_new_tokens=7) for p in prompts[5:]]
    eng.run()
    outs = [list(r.generated) for r in reqs]
    assert outs == refs
    leaked, bad = eng.pool.check_leaks()
    assert not leaked and not bad
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_engine_parity_llama_gqa():
    m = _tiny_llama()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (6, 10, 4)]
    refs = [_seq_ref(m, p, 5) for p in prompts]
    eng = LLMEngine(m, num_blocks=24, block_size=8, max_running=4)
    assert eng.generate_batch(prompts, max_new_tokens=5) == refs


def test_engine_eos_stops_request(gpt, gpt_engine):
    prompt = [1, 2, 3, 4, 5]
    first = _seq_ref(gpt, prompt, 1)[0]
    ref = _seq_ref(gpt, prompt, 6, eos=first)
    [out] = gpt_engine.generate_batch([prompt], max_new_tokens=6,
                                      eos_token_id=first)
    assert out == ref
    assert gpt_engine._finished[-1].finish_reason == "eos"
    assert len(out) < 6


def test_streaming_callbacks_order(gpt_engine):
    got, done = [], []
    req = gpt_engine.add_request([3, 1, 4, 1, 5], max_new_tokens=5,
                                 on_token=lambda r, t: got.append(t),
                                 on_finish=lambda r: done.append(r.id))
    gpt_engine.run()
    assert got == list(req.generated) and len(got) == 5
    assert done == [req.id]


def test_sampled_requests_deterministic_per_seed(gpt_engine):
    prompts = [[5, 6, 7], [9, 8, 7, 6]]
    kw = dict(max_new_tokens=6, do_sample=True, temperature=0.9,
              top_k=20, seed=123)
    a = gpt_engine.generate_batch(prompts, **kw)
    b = gpt_engine.generate_batch(list(reversed(prompts)), **kw)
    # per-request numpy stream: independent of batch order/composition
    assert a == list(reversed(b))


# ===================================================================
# the decode program picks a greedy row's token; the logits stay on the
# device until a row needs them
# ===================================================================
def _wide_gpt():
    """Heads 128 wide: under PADDLE_TPU_PALLAS=interpret the decode
    program runs the pallas kernel."""
    pt.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=1,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, tensor_parallel=False))


def _roots(run):
    """(what `run()` returned, the counts of the `serving.step` roots of
    the steps it made, in order).  A step counts the rows it DISPATCHED
    (`decode_rows`, `rows_chained`) and what it brought to the host of
    the program it landed: the one of the step before, or its own where
    a `do_sample` row makes the step wait in place."""
    from paddle_tpu.observability import trace
    trace.clear()
    out = run()
    roots = [s[6] for s in sorted(trace.spans(), key=lambda s: s[1])
             if s[0] == "serving.step"]
    trace.clear()
    return out, roots


def _total(roots, key):
    return sum(c[key] for c in roots)


def _on_the_host(eng):
    """Wrap `eng._emit` as the parent served every row: the step's
    float32 logits fetched, the finite test and the token on the host."""
    emit = eng._emit
    eng._emit = lambda req, row, now: emit(req, np.asarray(row), now)


@pytest.mark.parametrize("build, pallas", [
    (_tiny_gpt, None), (_wide_gpt, "interpret")], ids=["gather", "kernel"])
def test_the_programs_choice_is_the_hosts_argmax_over_the_fetched_row(
        build, pallas, monkeypatch):
    if pallas:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    model = build()
    eng = LLMEngine(model, num_blocks=24, block_size=8, max_running=4,
                    prefill_chunk=16)
    emit, host = eng._emit, {}

    def spy(req, row, now):
        # the parent's choice, over the row fetched through the view;
        # the view itself goes on, so the engine takes the program's
        assert not isinstance(row, np.ndarray)
        fetched = np.asarray(row)
        assert fetched.dtype == np.float32 and fetched.shape == (64,)
        assert row.finite == bool(np.isfinite(fetched).all())
        host.setdefault(req.id, []).append(int(np.argmax(fetched)))
        return emit(req, row, now)

    eng._emit = spy
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (5, 19, 9, 12)]
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for req, p in zip(reqs, prompts):
        assert req.generated == host[req.id] == _seq_ref(model, p, 6)
    assert eng.close() == ([], [])


def test_a_greedy_step_copies_no_logits_and_a_sampled_row_copies_them_once(
        gpt):
    kw = dict(num_blocks=24, block_size=8, max_running=4, prefill_chunk=16)
    greedy = [[5, 6, 7], [9, 8, 7, 6], [1, 2, 3, 4, 5]]
    drawn = dict(do_sample=True, temperature=0.9, top_k=20, seed=123)

    def serve(eng, sampled):
        reqs = [eng.add_request(p, max_new_tokens=6) for p in greedy]
        if sampled:
            reqs.append(eng.add_request([4, 4, 2], max_new_tokens=3,
                                        **drawn))
        eng.run()
        return [r.generated for r in reqs]

    eng = LLMEngine(gpt, **kw)
    alone, roots = _roots(lambda: serve(eng, False))
    assert _total(roots, "decode_rows") == 18
    assert _total(roots, "logit_rows_fetched") == 0
    # a step lands the picks of the step before
    assert [c["rows_picked_on_device"] for c in roots[1:]] \
        == [c["decode_rows"] for c in roots[:-1]]
    assert alone == [_seq_ref(gpt, p, 6) for p in greedy]

    mixed, roots = _roots(lambda: serve(eng, True))
    # the drawn row lives three steps: those wait for their own program
    # and copy its logits, all slots of them and once, and chain
    # nothing; the steps after it copy nothing and run ahead again
    with_drawn = [c for c in roots if c["decode_rows"] == 4]
    assert len(with_drawn) == 3 and len(roots) > 3
    for c in with_drawn:
        assert c["logit_rows_fetched"] == eng.max_running
        assert c["rows_picked_on_device"] == c["rows_chained"] == 0
    after = roots[max(i for i, c in enumerate(roots)
                      if c["decode_rows"] == 4) + 1:]
    assert after and _total(after, "logit_rows_fetched") == 0
    assert _total(after, "rows_picked_on_device") \
        == _total(after, "decode_rows") == 9
    # the first of them follows a step that left nothing in flight
    assert _total(after, "rows_chained") == 6
    # both kinds of row produce what the host's path produces
    host = LLMEngine(gpt, **kw)
    _on_the_host(host)
    assert mixed == serve(host, True)
    assert mixed[:3] == alone


@pytest.mark.parametrize("how", ["poison", "nan"])
def test_a_row_that_is_not_finite_fails_alone_and_copies_no_logits(
        how, monkeypatch):
    """chaos `serving.request_poison`, or a NaN that the decode program
    itself finds in one row: that request ends with `error` and no
    token, the others are served as if it were not there."""
    import jax.numpy as jnp
    from paddle_tpu.resilience import chaos
    from paddle_tpu.tensor import Tensor
    model = _tiny_gpt()
    prompts = [[5, 6, 7], None, [1, 2, 3, 4, 5]]
    refs = [p and _seq_ref(model, p, 5) for p in prompts]
    # a token that only the second request is ever fed
    odd = next(t for t in range(63, 0, -1)
               if t not in prompts[0] + prompts[2] + refs[0] + refs[2])
    prompts[1] = [9, 8, 7, odd]
    if how == "nan":
        forward = model.forward

        def planted(ids, caches=None, **kw):
            # one NaN in the logits of the row that is fed that token
            logits = forward(ids, caches=caches, **kw)._array
            bad = (ids._array[:, -1] == odd)[:, None, None] \
                & (jnp.arange(logits.shape[-1]) == 11)
            return Tensor._from_array(jnp.where(bad, jnp.nan, logits))

        monkeypatch.setattr(model, "forward", planted)
    eng = LLMEngine(model, num_blocks=24, block_size=8, max_running=4,
                    prefill_chunk=16)

    def serve():
        with chaos.scoped("serving.request_poison@2" if how == "poison"
                          else ""):
            reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
            eng.run()
        return reqs

    reqs, roots = _roots(serve)
    assert [r.finish_reason for r in reqs] == ["length", "error", "length"]
    assert reqs[1].generated == []
    assert [r.generated for r in reqs[::2]] == refs[::2]
    assert _total(roots, "logit_rows_fetched") == 0
    # the finite test is seen one step late: the row already chained on
    # the failed request's pick is thrown away, and that is all
    assert _total(roots, "rows_dropped") == 1
    assert _total(roots, "decode_rows") == 5 + 2 + 5
    assert eng.close() == ([], [])


def test_a_wrapped_emit_reads_the_row_and_an_altered_row_is_sampled_on_host(
        gpt):
    """What the benchmark's altered-token fault does: `np.array(row)`
    gives the float32 logits row, and an ndarray handed on is tested and
    sampled on the host."""
    eng = LLMEngine(gpt, num_blocks=24, block_size=8, max_running=4,
                    prefill_chunk=16)
    emit, seen = eng._emit, []

    def altered(req, row, now):
        row = np.array(row)
        assert row.dtype == np.float32 and row.flags.writeable
        best = int(np.argmax(row))
        row[best] = row.min() - 1.0
        seen.append((req.id, best, int(np.argmax(row))))
        return emit(req, row, now)

    eng._emit = altered
    prompts = [[5, 6, 7], [9, 8, 7, 6]]

    def serve():
        reqs = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        eng.run()
        return reqs

    reqs, roots = _roots(serve)
    for req, p in zip(reqs, prompts):
        mine = [s for s in seen if s[0] == req.id]
        assert req.generated == [second for _, _, second in mine]
        assert all(best != second for _, best, second in mine)
        # the stream the host chose is the stream the model was fed: the
        # best of every row is the sequential path's next token after
        # the tokens EMITTED so far, although the row chained on the
        # program's own pick was already in flight
        for k, (_, best, _) in enumerate(mine):
            assert best == _seq_ref(gpt, p + req.generated[:k], 1)[0]
    # every program that brought a token had its logits copied, once:
    # the two requests run in step, four tokens each
    assert {c["logit_rows_fetched"] for c in roots} == {0, eng.max_running}
    assert _total(roots, "logit_rows_fetched") == 4 * eng.max_running
    assert _total(roots, "rows_picked_on_device") == 0
    # each altered token but a request's last costs the row that had
    # chained on the program's pick
    assert _total(roots, "rows_dropped") \
        == _total(roots, "rows_chained") == 2 * 3
    assert eng.pool.check_leaks() == ([], [])
    # a row of NaN handed on fails its request on the host's test
    eng._emit = lambda req, row, now: emit(
        req, np.full(64, np.nan, np.float32), now)
    req = eng.add_request([3, 1, 4], max_new_tokens=3)
    eng.run()
    assert req.finish_reason == "error" and req.generated == []


# ===================================================================
# the engine runs one decode program ahead of the host: a greedy row's
# next token is the pick of the program before, taken on the device
# ===================================================================
KW = dict(num_blocks=48, block_size=8, max_running=6, prefill_chunk=16)


def test_chained_rows_are_token_identical_and_a_length_finish_wastes_none(
        gpt):
    """Interleaved requests of mixed lengths: every row after a
    request's first takes its token from the device, the streams are the
    sequential path's, and no program carries a row whose request had its
    last pick in flight: rows dispatched = tokens generated."""
    eng = LLMEngine(gpt, **KW)
    rng = np.random.RandomState(4)
    lens, news = (5, 23, 3, 9, 14, 7), (1, 6, 9, 2, 4, 7)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in lens]
    refs = [_seq_ref(gpt, p, n) for p, n in zip(prompts, news)]

    def serve():
        reqs = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts[:3], news)]
        for _ in range(3):
            eng.step()
        reqs += [eng.add_request(p, max_new_tokens=n)
                 for p, n in zip(prompts[3:], news[3:])]
        eng.run()
        return reqs

    reqs, roots = _roots(serve)
    assert [r.generated for r in reqs] == refs
    assert {r.finish_reason for r in reqs} == {"length"}
    assert _total(roots, "decode_rows") == sum(news)
    assert _total(roots, "rows_chained") == sum(news) - len(news)
    assert _total(roots, "rows_dropped") == 0
    assert _total(roots, "rows_picked_on_device") == sum(news)
    assert all(r.in_flight == 0 for r in reqs)
    assert eng.close() == ([], [])


def test_an_eos_is_seen_one_step_late_and_its_surplus_pick_is_dropped(gpt):
    eng = LLMEngine(gpt, **KW)
    prompt, other = [1, 2, 3, 4, 5], [9, 8, 7, 6]
    ref = _seq_ref(gpt, prompt, 8)
    k = next(i for i in range(1, 8) if ref[i] not in ref[:i])
    got, done = [], []

    def serve():
        a = eng.add_request(prompt, max_new_tokens=8, eos_token_id=ref[k],
                            on_token=lambda r, t: got.append(t),
                            on_finish=lambda r: done.append(
                                eng.pool.free_blocks))
        b = eng.add_request(other, max_new_tokens=8)
        eng.run()
        return a, b

    (a, b), roots = _roots(serve)
    assert a.finish_reason == "eos"
    assert got == a.generated == ref[:k + 1]     # nothing after the EOS
    assert b.generated == _seq_ref(gpt, other, 8)
    # the row that had chained on the EOS was in flight when it landed:
    # thrown away, and that is the one row the run wasted
    assert _total(roots, "rows_dropped") == 1
    assert _total(roots, "decode_rows") == (k + 1) + 1 + 8
    # its blocks went home when it finished, not when the row landed
    assert len(done) == 1 and done[0] > 0
    assert eng.pool.free_blocks == eng.pool.num_blocks
    assert eng.pool.check_leaks() == ([], [])


def test_a_preemption_victim_with_a_pick_in_flight_resumes_identically(gpt):
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 64, size=n).tolist()
               for n in (7, 11, 5, 9, 6, 4)]
    refs = [_seq_ref(gpt, p, 8) for p in prompts]
    eng = LLMEngine(gpt, num_blocks=6, block_size=4, max_running=6,
                    prefill_chunk=8)
    preempt, seen = eng.scheduler.preempt, []

    def spy(req):
        seen.append((req.in_flight, len(req.generated)))
        preempt(req)

    eng.scheduler.preempt = spy
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    eng.run()
    # a victim whose pick was still out keeps it: the pick is emitted
    # when it lands, and the prefix prefilled again covers it
    assert any(flying for flying, _ in seen)
    assert [r.generated for r in reqs] == refs
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == eng.pool.num_blocks


def _drawn_ref(model, prompt, n, temperature, top_k, seed):
    """The stream a `do_sample` request draws: the full forward's
    float32 row through `filter_logits`, one numpy Generator a position
    seeded by (seed, position)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.text.generation import filter_logits
    out = []
    for _ in range(n):
        with pt.no_grad():
            row = model(pt.to_tensor(np.asarray([prompt + out], "int64"))
                        ).numpy()[0, -1].astype(np.float32)
        kept = filter_logits(jnp.asarray(row)[None, :], temperature, top_k,
                             None)[0]
        p = np.asarray(jax.nn.softmax(kept), dtype=np.float64)
        out.append(int(np.random.default_rng([seed, len(out)]).choice(
            len(p), p=p / p.sum())))
    return out


def test_a_do_sample_row_draws_todays_stream_and_its_steps_chain_nothing(
        gpt):
    eng = LLMEngine(gpt, **KW)
    greedy, drawn = [[5, 6, 7], [9, 8, 7, 6, 5, 4]], [4, 4, 2]

    def serve():
        reqs = [eng.add_request(p, max_new_tokens=9) for p in greedy]
        for _ in range(3):      # the greedy rows run ahead of the host
            eng.step()
        assert eng._flight is not None
        reqs.append(eng.add_request(drawn, max_new_tokens=4, do_sample=True,
                                    temperature=0.9, top_k=20, seed=123))
        eng.run()
        return reqs

    reqs, roots = _roots(serve)
    assert reqs[2].generated == _drawn_ref(gpt, drawn, 4, 0.9, 20, 123)
    assert [r.generated for r in reqs[:2]] \
        == [_seq_ref(gpt, p, 9) for p in greedy]
    # the steps that hold the drawn row wait for their own program: they
    # chain nothing, and neither does the step after the last of them
    held = [i for i, c in enumerate(roots) if c["logit_rows_fetched"]]
    assert len(held) == 4
    for i in held + [held[-1] + 1]:
        assert roots[i]["rows_chained"] == 0
    assert roots[held[0] - 1]["rows_chained"] == 2
    assert roots[held[-1] + 2]["rows_chained"] == 2
    assert _total(roots, "rows_dropped") == 0
    assert _total(roots, "decode_rows") == 9 + 9 + 4


@pytest.mark.parametrize("how", ["cancel", "ttl", "close", "drain-ttl"])
def test_a_request_that_ends_with_a_pick_in_flight_leaves_no_leak(gpt, how):
    eng = LLMEngine(gpt, **KW)
    done = []
    other = eng.add_request([9, 8, 7, 6], max_new_tokens=6)
    req = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=6,
                          on_finish=lambda r: done.append(r.finish_reason))
    while not req.generated:
        eng.step()
    assert req.in_flight == 1 and eng._flight is not None
    had = list(req.generated)
    if how == "cancel":
        eng.cancel(req)
    elif how == "ttl":
        req.ttl_s = 0.0
    elif how == "close":
        assert eng.close() == ([], [])
    else:
        eng.drain(ttl_s=-1.0)
    if how in ("cancel", "ttl"):
        assert eng.has_work
        _, roots = _roots(eng.run)
        # its pick in flight is thrown away, with the row that had
        # chained on it where the expiry was seen a step late
        assert 1 <= _total(roots, "rows_dropped") <= 2
        assert other.generated == _seq_ref(gpt, [9, 8, 7, 6], 6)
        assert eng.pool.free_blocks == eng.pool.num_blocks
    else:
        assert other.finish_reason == "drained"
    assert done == [{"cancel": "cancelled", "ttl": "expired-ttl"}.get(
        how, "drained")]
    assert req.generated == had             # nothing after the end
    assert not eng.has_work and eng._flight is None
    assert eng.pool.check_leaks() == ([], [])


def test_the_decode_program_is_traced_and_compiled_once(gpt):
    """Host-fed rows, chained rows, rows of both kinds in one program and
    a step that waits in place: one argument form, one executable."""
    eng = LLMEngine(gpt, **KW)
    traced, build = [], eng._build_decode

    def counting(*a, **kw):
        traced.append(1)
        return build(*a, **kw)

    eng._build_decode = counting
    eng.generate_batch([[1, 2, 3]], max_new_tokens=3)
    reqs = [eng.add_request([5, 6, 7], max_new_tokens=5)]
    eng.step()
    eng.step()
    reqs.append(eng.add_request([4, 4], max_new_tokens=2, do_sample=True))
    reqs.append(eng.add_request([9, 8, 7, 6], max_new_tokens=5))
    eng.run()
    assert all(r.finish_reason == "length" for r in reqs)
    assert len(traced) == 1
    assert eng._programs[("decode",)]._cache_size() == 1
    assert eng.close() == ([], [])


def test_the_decode_program_compiles_once_under_an_mp_mesh():
    """Under the fleet mesh the program returns its ids replicated and
    committed: the zeros that stand for them before the first step have
    that form too, so the second call finds the first's executable."""
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.set_mesh(mesh_mod.build_mesh(mp=2))
    try:
        pt.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=64, hidden_dropout=0.0,
            attention_dropout=0.0, tensor_parallel=True))
        eng = LLMEngine(model, num_blocks=24, block_size=8, max_running=4,
                        prefill_chunk=16)
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]
        outs = eng.generate_batch(prompts, max_new_tokens=6)
        assert eng._programs[("decode",)]._cache_size() == 1
        assert outs == [_seq_ref(model, p, 6) for p in prompts]
        assert eng.close() == ([], [])
    finally:
        mesh_mod.clear_mesh()


def test_has_work_holds_until_the_last_token_is_emitted(gpt):
    eng = LLMEngine(gpt, **KW)
    req = eng.add_request([3, 1, 4, 1, 5], max_new_tokens=1)
    first = eng.step()
    # the one row is dispatched; its pick is still on the device
    assert (first["decoded"], first["emitted"]) == (1, 0)
    assert req.generated == [] and req.in_flight == 1 and eng.has_work
    last = eng.step()
    # nothing to dispatch (the pick in flight is the request's last):
    # the step lands it
    assert (last["decoded"], last["emitted"]) == (0, 1)
    assert req.generated == _seq_ref(gpt, [3, 1, 4, 1, 5], 1)
    assert req.finish_reason == "length" and not eng.has_work
    # drain() delivers what is in flight too
    reqs = [eng.add_request(p, max_new_tokens=4)
            for p in ([5, 6, 7], [9, 8, 7, 6])]
    eng.step()
    eng.step()
    assert eng.drain()["drained"] == 0
    assert [r.generated for r in reqs] \
        == [_seq_ref(gpt, p, 4) for p in ([5, 6, 7], [9, 8, 7, 6])]
    assert eng.close() == ([], [])


# ===================================================================
# block pool invariants
# ===================================================================
def test_block_pool_alloc_free_refcount():
    pool = BlockPool(num_layers=1, num_blocks=8, block_size=4,
                     planes={"k": (2, 8), "v": (2, 8)})
    a = pool.allocate(3)
    assert len(a) == 3 and pool.free_blocks == 5
    pool.ref(a)                       # rc 2
    pool.free(a)                      # rc 1 — still held
    assert pool.free_blocks == 5
    pool.free(a)                      # rc 0 — home
    assert pool.free_blocks == 8
    with pytest.raises(ValueError):
        pool.free(a)                  # double free
    b = pool.allocate(8)
    assert pool.allocate(1) is None   # exhausted -> None, not a raise
    with pytest.raises(PoolExhausted):
        pool.allocate(9)              # can never fit -> hard error
    pool.free(b)
    assert pool.check_leaks() == ([], [])
    with pytest.raises(ValueError):
        pool.ref([0])                 # ref of an unallocated block


def test_block_pool_blocks_for():
    pool = BlockPool(1, 8, 16, {"k": (2, 8), "v": (2, 8)})
    assert [pool.blocks_for(n) for n in (1, 16, 17, 32)] == [1, 1, 2, 2]


def test_a_fresh_pool_hands_out_its_first_ids_in_order():
    pool = BlockPool(1, 8, 4, {"k": (2, 8)})
    assert pool.allocate(5) == [0, 1, 2, 3, 4]
    assert pool.allocate(3) == [5, 6, 7]


@pytest.mark.parametrize("seed", range(3))
def test_the_pool_hands_out_its_lowest_free_ids_first(seed):
    """Over cycles of allocations and frees each allocation is the
    lowest free ids, ascending, and no id is out twice."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(1, 48, 4, {"k": (2, 8)})
    held = []
    for _ in range(200):
        if held and rng.random() < 0.45:
            pool.free(held.pop(int(rng.integers(len(held)))))
            continue
        n = int(rng.integers(1, 9))
        out = {b for table in held for b in table}
        free = [b for b in range(48) if b not in out]
        got = pool.allocate(n)
        if n > len(free):
            assert got is None
            continue
        assert got == free[:n]
        held.append(got)
    for table in held:
        pool.free(table)
    assert pool.check_leaks() == ([], [])
    assert pool.allocate(48) == list(range(48))


def test_a_shared_block_comes_back_only_at_refcount_zero():
    pool = BlockPool(1, 8, 4, {"k": (2, 8)})
    a = pool.allocate(3)                    # 0, 1, 2
    pool.ref([1])                           # another table shares 1
    pool.free(a)
    assert pool.allocate(3) == [0, 2, 3]    # 1 is still out
    pool.free([1])
    assert pool.allocate(1) == [1]


def _tiny_latent():
    from paddle_tpu.text.deepseek import (DeepseekV3Config,
                                          DeepseekV3ForCausalLM)
    pt.seed(0)
    return DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position_embeddings=64, kv_lora_rank=24,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        moe_intermediate_size=16, n_routed_experts=4, n_shared_experts=1,
        num_experts_per_tok=2))


@pytest.mark.parametrize("build, planes", [
    (_tiny_gpt, {"k": (4, 8), "v": (4, 8)}),
    (_tiny_llama, {"k": (2, 8), "v": (2, 8)}),       # GQA: unrepeated
    # ONE latent row a token: [c 24 | k_rope 8] padded to 128 lanes
    (_tiny_latent, {"kv": (128,)})], ids=["gpt", "llama-gqa", "latent"])
def test_the_pool_allocates_the_planes_the_model_declares(build, planes):
    model = build()
    assert model.cache_planes() == [planes] * 2
    pool = BlockPool.for_model(model, num_blocks=6, block_size=4)
    assert pool.plane_shapes() == {
        name: (6, 4) + trailing for name, trailing in planes.items()}
    assert all(len(arrays) == 2 for arrays in pool.planes.values())
    # the allocator never sees the layout
    assert pool.blocks_for(9) == 3 and len(pool.allocate(6)) == 6
    assert pool.allocate(1) is None
    pool.release()
    assert pool.planes == {}


@pytest.mark.parametrize("build", [_tiny_gpt, _tiny_latent],
                         ids=["gpt", "latent"])
def test_engine_threads_the_pools_planes_through_both_programs(build):
    """Whatever the planes, the programs take and return them by name,
    `program_structs` describes them, the greedy output equals the
    sequential `generate`, and `close()` releases them."""
    model = build()
    eng = LLMEngine(model, num_blocks=24, block_size=4, max_running=3,
                    prefill_chunk=8)
    names = sorted(eng.pool.planes)
    for key in eng.program_keys():
        _, structs = eng.program_structs(key)
        assert sorted(structs[2]) == names
        assert [s.shape for s in structs[2][names[0]]] == \
            [a.shape for a in eng.pool.planes[names[0]]]
    prompts = [np.arange(3, 3 + n) % 64 for n in (11, 4, 7)]
    outs = eng.generate_batch(prompts, max_new_tokens=5)
    for p, got in zip(prompts, outs):
        assert got == _seq_ref(model, p, 5)
    assert sorted(eng.pool.planes) == names
    assert eng.close() == ([], [])
    assert eng.pool.planes == {}


@pytest.mark.parametrize("pos, limit, width, real", [
    ([0], [5], 8, 5),                   # a chunk of 5 padded to 8
    ([6], [9], 4, 3),                   # a later chunk
    ([3, 0, 1], [4, 0, 2], 1, 2)],      # a decode step with a dead slot
    ids=["chunk", "later-chunk", "decode"])
def test_a_routed_layer_reports_the_load_of_the_real_tokens(pos, limit,
                                                             width, real):
    """Under a paged cache a routed layer leaves, beside its result, how
    many of the tokens from `pos` up to `limit` each expert received: the
    serving engine's `moe_assignments` and `experts_touched`."""
    import jax.numpy as jnp
    from paddle_tpu.tensor import Tensor
    m = _tiny_latent()
    m.eval()
    pool = BlockPool.for_model(m, num_blocks=8, block_size=4)
    rows = len(pos)
    table = np.tile(np.arange(3, dtype=np.int32), (rows, 1)) \
        + 3 * np.arange(rows, dtype=np.int32)[:, None] % 6
    caches = [{"kv": Tensor._from_array(pool.planes["kv"][i]),
               "table": Tensor._from_array(jnp.asarray(table)),
               "pos": Tensor._from_array(jnp.asarray(pos, jnp.int32)),
               "limit": Tensor._from_array(jnp.asarray(limit, jnp.int32))}
              for i in range(pool.num_layers)]
    with pt.no_grad():
        m(pt.randint(0, 64, [rows, width]), caches=caches)
    assert "expert_load" not in caches[0]           # the dense layer
    load = np.asarray(caches[1]["expert_load"]._array)
    assert load.shape == (4,) and load.sum() == real * 2


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_block_pool_random_interleavings_property(seed):
    """Property test (hypothesis-style seeded loop): ANY interleaving
    of allocate / ref / free / preempt-style bulk-free ends with a full
    free list and zero refcount drift — including orderings the engine
    never produces today.  A shadow refcount model checks every
    intermediate state; `check_leaks()` must come back clean after the
    final teardown."""
    rng = np.random.RandomState(seed)
    pool = BlockPool(num_layers=1, num_blocks=16, block_size=4,
                     planes={"k": (2, 8), "v": (2, 8)})
    shadow = {}                 # block id -> refcount (held blocks only)
    tables = []                 # simulated per-request block tables

    for _ in range(300):
        op = rng.randint(4)
        if op == 0:                                   # allocate
            n = int(rng.randint(1, 5))
            got = pool.allocate(n)
            if n > pool.num_blocks - sum(
                    1 for r in shadow.values() if r > 0):
                # more than physically free: must refuse, not corrupt
                assert got is None or len(got) == n
            if got is None:
                continue
            assert len(set(got)) == n
            assert not any(b in shadow and shadow[b] > 0 for b in got)
            for b in got:
                shadow[b] = 1
            tables.append(list(got))
        elif op == 1 and tables:                      # ref (share)
            t = tables[int(rng.randint(len(tables)))]
            pool.ref(t)
            tables.append(list(t))
            for b in t:
                shadow[b] += 1
        elif op == 2 and tables:                      # free one table
            t = tables.pop(int(rng.randint(len(tables))))
            pool.free(t)
            for b in t:
                shadow[b] -= 1
        elif op == 3 and tables:                      # preempt: bulk free
            k = int(rng.randint(1, len(tables) + 1))
            for _ in range(k):
                t = tables.pop()
                pool.free(t)
                for b in t:
                    shadow[b] -= 1
        # shadow model and pool must agree at EVERY step
        held = sum(1 for r in shadow.values() if r > 0)
        assert pool.free_blocks == pool.num_blocks - held
        assert pool._refs == [shadow.get(b, 0)
                              for b in range(pool.num_blocks)]
        assert all(r >= 0 for r in shadow.values())

    for t in tables:            # teardown: everything goes home
        pool.free(t)
    assert pool.check_leaks() == ([], [])
    assert pool.free_blocks == pool.num_blocks
    assert sorted(pool._free) == list(range(pool.num_blocks))


# ===================================================================
# preemption and resume mid-decode
# ===================================================================
def test_preemption_resume_mid_decode_parity(gpt):
    m = gpt
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 64, size=n).tolist()
               for n in (7, 11, 5, 9, 6, 4)]
    refs = [_seq_ref(m, p, 8) for p in prompts]
    # 6 blocks of 4 tokens cannot hold 6 requests of 12-19 tokens:
    # preemption MUST fire, and evicted requests re-prefill + resume
    eng = LLMEngine(m, num_blocks=6, block_size=4, max_running=6,
                    prefill_chunk=8)
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    eng.run()
    assert sum(r.preemptions for r in reqs) >= 1
    assert [list(r.generated) for r in reqs] == refs
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_preempted_request_keeps_queue_front(gpt):
    eng = LLMEngine(gpt, num_blocks=4, block_size=4, max_running=2,
                    prefill_chunk=8)
    a = eng.add_request([1] * 9, max_new_tokens=6)
    b = eng.add_request([2] * 9, max_new_tokens=6)
    eng.run()
    assert a.finish_reason == "length" and b.finish_reason == "length"
    leaked, bad = eng.pool.check_leaks()
    assert not leaked and not bad


# ===================================================================
# paged attention: pallas (interpret) vs the jnp gather fallback
# ===================================================================
def test_paged_attention_pallas_matches_fallback():
    import jax.numpy as jnp
    from paddle_tpu.ops.nn_kernels import paged_attention_k
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.RandomState(0)
    # D = 128: the kernel serves lane-aligned head dims only (the pool
    # is never padded in-call; others take the gather fallback)
    B, H, Hkv, D, bs, N, M = 3, 4, 2, 128, 8, 12, 4
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(N, bs, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(N, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(rng.permutation(N)[:B * M].reshape(B, M),
                         jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    assert pa.supports(q.shape, kp.shape, q.dtype)
    ref = np.asarray(paged_attention_k(q, kp, vp, tables, pos))
    out = np.asarray(pa.paged_decode_attention(q, kp, vp, tables, pos + 1,
                                               interpret=True))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def _ragged_case(rng, M, bs, Hkv, g, dtype, chunk):
    """Rows of every kind of length the walk meets, each in its own
    blocks; columns past a row's context name a block of NaNs (POISON),
    so a walk that reduces one shows.  The last row is a dead slot as
    the engine builds it: length 1, table all zero."""
    import jax.numpy as jnp
    full = M * bs
    lens = [1, min(chunk * bs, full), min(2 * bs, full),
            min(2 * bs + 1, full), max(full - bs - 3, 1), full, 1]
    B, D = len(lens), 128
    N = sum(-(-n // bs) for n in lens) + 2          # + block 0 and POISON
    poison = N - 1
    tables = np.full((B, M), poison, np.int32)
    free = list(rng.permutation(np.arange(1, N - 1)))
    for b, n in enumerate(lens[:-1]):
        for j in range(-(-n // bs)):
            tables[b, j] = free.pop()
    tables[-1] = 0
    q = rng.randn(B, 1, g * Hkv, D).astype(np.float32)
    k = rng.randn(N, bs, Hkv, D).astype(np.float32)
    v = rng.randn(N, bs, Hkv, D).astype(np.float32)
    # the values both sides see are the pool dtype's own
    q, k, v = (np.array(jnp.asarray(a, dtype).astype(jnp.float32))
               for a in (q, k, v))
    return q, k, v, tables, np.asarray(lens, np.int32), poison


@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("M,bs,Hkv,dtype,chunk", [
    (4, 8, 2, "float32", 4),        # a table shorter than a chunk
    (7, 16, 16, "float32", 4),      # a table that ends inside a chunk
    (7, 16, 16, "bfloat16", 7),
    (128, 16, 16, "bfloat16", 8),   # the benchmark's cells
    (128, 16, 16, "float32", 4),
    (128, 16, 8, "bfloat16", 16),   # ... under mp 2; the hybrid cell's
    (40, 16, 8, "bfloat16", 16),    # ends inside a chunk of 16
    (34, 16, 8, "float32", 8),
    (9, 16, 16, "float32", 4),      # a last chunk of one block
    (34, 16, 16, "bfloat16", 8),
    (34, 8, 4, "float32", 32),      # kv heads short of a sublane tile
    (34, 16, 1, "bfloat16", 16),    # one kv head: a shard of few
])
def test_paged_kernel_walks_ragged_rows_like_the_fallback(
        M, bs, Hkv, dtype, chunk, g):
    """In interpret mode a scratch row that no copy wrote reads as NaN:
    a product that takes one in shows."""
    import jax.numpy as jnp
    from paddle_tpu.ops.nn_kernels import paged_attention_k
    from paddle_tpu.ops.pallas import paged_attention as pa
    assert pa.chunk_blocks(M, bs, Hkv, 128, dtype) == chunk
    rng = np.random.RandomState(M + g)
    q, k, v, tables, lens, poison = _ragged_case(
        rng, M, bs, Hkv, g, dtype, chunk)
    clean_k, clean_v = k.copy(), v.copy()
    k[poison] = v[poison] = np.nan
    clean_k[poison] = clean_v[poison] = 0.0
    ref = np.asarray(paged_attention_k(
        jnp.asarray(q), jnp.asarray(clean_k), jnp.asarray(clean_v),
        jnp.asarray(tables), jnp.asarray(lens - 1)))
    out = pa.paged_decode_attention(
        jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True)
    assert out.dtype == jnp.dtype(dtype)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)     # the output's own rounding
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), ref,
                               **tol)


@pytest.mark.parametrize("H,Hkv", [(64, 8), (16, 16), (16, 8)])
def test_paged_kernel_keeps_16_bits_of_p_in_a_bfloat16_pool(H, Hkv):
    """A long row of flat scores under one peak: every p but the peak's
    is the same number c = 0.50131, which bfloat16 rounds to 0.5, 0.26%
    low, always the same way.  With every value 1 the answer is 1; a
    body that rounds p to bfloat16 before `p . v` while it sums p in
    float32 gives 0.9974, which the output's own rounding takes to
    0.99609."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    bs, M, D, n = 16, 64, 128, 1000
    N = M + 1
    q = np.zeros((1, 1, H, D), np.float32)
    q[..., 0] = 8.0
    k = np.zeros((N, bs, Hkv, D), np.float32)
    k[3, 5, :, 0] = 0.9765625       # the peak: 8 x 0.9765625 / sqrt(128)
    v = np.ones((N, bs, Hkv, D), np.float32)
    tables = np.arange(1, N, dtype=np.int32)[None]
    out = pa.paged_decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), jnp.asarray(tables),
        jnp.asarray([n], jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), 1.0,
                               atol=1e-3)


@pytest.mark.parametrize("H,Hkv,dtype", [
    (64, 8, "bfloat16"),        # the hybrid cell's GQA layer
    (32, 8, "bfloat16"),
    (16, 16, "bfloat16"),       # the gpt3-1.3b cells
    (16, 16, "float32"),
])
def test_a_chunk_is_two_products_whatever_the_heads(H, Hkv, dtype):
    """One body for every call: the kernel's program holds the scores'
    product and the one product against V (p's two halves stacked as
    2 H rows in a 16-bit pool), and no second pass over V."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    s = jax.ShapeDtypeStruct
    q, pool = (3, 1, H, 128), (12, 8, Hkv, 128)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: pa.paged_decode_attention(*a, interpret=True))(
        s(q, dtype), s(pool, dtype), s(pool, dtype),
        s((3, 4), jnp.int32), s((3,), jnp.int32)))
    assert jaxpr.count(" dot_general[") == 2
    rows = H if dtype == "float32" else 2 * H
    assert f"f32[{rows},128] = dot_general[" in jaxpr


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2), (8, 1)],
                         ids=["dense", "gqa-2", "mqa-8"])
def test_six_requests_served_through_the_kernel_as_through_the_gather(
        monkeypatch, heads, kv_heads):
    """What the benchmark's `served_logit_gap` compares, request by
    request, in float32 where rounding hides nothing: the same six
    requests through the gather and through the kernel (interpreted)
    emit the same tokens, every served position's logits agree, and no
    served token's logit lies below the gather's best."""
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=128 * heads, num_layers=2,
        num_heads=heads, num_kv_heads=kv_heads, intermediate_size=64,
        max_position_embeddings=64, tensor_parallel=False))
    rng = np.random.RandomState(heads)
    prompts = [rng.randint(0, 64, size=n).tolist()
               for n in (5, 19, 9, 1, 33, 16)]

    def serve():
        eng = LLMEngine(model, num_blocks=40, block_size=8, max_running=4,
                        prefill_chunk=16)
        rows, emit = {}, eng._emit

        def keep(req, row, now):
            rows.setdefault(req.id, []).append(np.array(row))
            return emit(req, row, now)

        eng._emit = keep
        reqs = [eng.add_request(p, max_new_tokens=k)
                for p, k in zip(prompts, (6, 3, 8, 12, 4, 7))]
        eng.run()
        return [(r.generated, np.stack(rows[r.id])) for r in reqs]

    gathered = serve()
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    for (tokens, ref), (got, logits) in zip(gathered, serve()):
        assert got == tokens
        np.testing.assert_allclose(logits, ref, rtol=2e-4, atol=2e-5)
        took = ref[np.arange(len(got)), got]
        assert float((ref.max(axis=1) - took).max()) == 0.0


def test_walked_blocks_are_the_blocks_the_kernel_touches():
    """Column j of every table made a block of NaNs in turn: a row's
    output shows whether its walk reached that column."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(5)
    B, M, bs, Hkv, D = 5, 7, 16, 16, 128
    assert pa.chunk_blocks(M, bs, Hkv, D, "float32") == 4   # two chunks
    lens = np.asarray([1, 16, 17, 48, 112], np.int32)
    N = B * M + 1
    k = rng.randn(N, bs, Hkv, D).astype(np.float32)
    v = rng.randn(N, bs, Hkv, D).astype(np.float32)
    k[N - 1] = v[N - 1] = np.nan
    q = jnp.asarray(rng.randn(B, 1, Hkv, D), jnp.float32)
    clean = rng.permutation(N - 1).reshape(B, M).astype(np.int32)
    touched = 0
    for j in range(M):
        tables = clean.copy()
        tables[:, j] = N - 1
        out = np.asarray(pa.paged_decode_attention(
            q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
            jnp.asarray(lens), interpret=True))
        touched += int(np.isnan(out).any(axis=(1, 2, 3)).sum())
    assert touched == pa.walked_blocks(lens, M, bs) == 1 + 1 + 2 + 3 + 7
    assert touched < B * M
    # a length of 0 walks as a dead slot does, a length past the table
    # stops at the table's end
    assert pa.walked_blocks([0, 1, 10 ** 6], M, bs) == 1 + 1 + M


@pytest.mark.parametrize("mode,q,pool,dtype,ragged", [
    ("interpret", (3, 1, 4, 128), (12, 8, 2, 128), "float32", True),
    ("interpret", (3, 1, 4, 64), (12, 8, 2, 64), "float32", False),
    ("interpret", (3, 1, 4, 128), (12, 8, 2, 128), "float16", False),
    ("0", (3, 1, 4, 128), (12, 8, 2, 128), "float32", False),
], ids=["kernel", "head-dim-64", "float16", "pallas-off"])
def test_blocks_read_follow_the_path_that_serves_the_call(
        monkeypatch, mode, q, pool, dtype, ragged):
    """The count is the kernel's walk only where the kernel runs: the
    XLA fallback gathers every column of every row's table."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setenv("PADDLE_TPU_PALLAS", mode)
    lens, M = [1, 9, 30], 4
    got = plo.paged_blocks_read(lens, M, q, pool, jnp.dtype(dtype))
    assert got == (1 + 2 + 4 if ragged else 3 * M)
    s = jax.ShapeDtypeStruct
    # (a lambda: a trace of the function itself is cached across modes)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: plo.paged_attention_with_pallas(*a))(
        s(q, dtype), s(pool, dtype), s(pool, dtype),
        s((3, M), jnp.int32), s((3,), jnp.int32)))
    assert ("pallas_call" in jaxpr) == ragged


def test_paged_kernel_takes_the_scale_as_any_host_number():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 1, 2, 128), jnp.float32)
    kp = jnp.asarray(rng.randn(5, 8, 2, 128), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([3, 12], jnp.int32)
    outs = [np.asarray(pa.paged_decode_attention(
        q, kp, kp, tables, lens, scale=s, interpret=True))
        for s in (0.25, np.float32(0.25), jnp.asarray(0.25))]
    assert (outs[0] == outs[1]).all() and (outs[0] == outs[2]).all()


def test_paged_attention_supports_gate():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    ok = ((3, 1, 4, 128), (12, 8, 2, 128))
    assert pa.supports(*ok, jnp.float32)
    # more query rows a request are the prefill kernel's, any number of
    # them; a 16-bit pool's kv heads come apart in pairs (or there is
    # one), and a chunk that cannot be cut in tiles has to fit whole
    assert pa.supports((3, 2, 4, 128), ok[1], jnp.float32)
    assert pa.supports((1, 1000, 24, 128), (12, 8, 8, 128), jnp.bfloat16)
    assert not pa.supports((1, 1032, 24, 128), (12, 8, 8, 128),
                           jnp.bfloat16)        # 1,032 = 8 x 129 rows
    assert pa.supports((1, 64, 6, 128), (12, 8, 3, 128), jnp.float32)
    assert not pa.supports((1, 64, 6, 128), (12, 8, 3, 128), jnp.bfloat16)
    assert pa.supports((1, 64, 6, 128), (12, 8, 1, 128), jnp.bfloat16)
    assert pa.supports((1, 64, 8, 128), (12, 8, 4, 128), jnp.bfloat16, mp=2)
    assert pa.supports(ok[0], (12, 6, 2, 128), jnp.float32)  # any bs
    assert not pa.supports(ok[0], ok[1], jnp.float16)  # Mosaic refuses
    assert not pa.supports(ok[0], (12, 8, 3, 128), jnp.float32)  # H % Hkv
    assert not pa.supports((3, 1, 4, 64), (12, 8, 2, 64),
                           jnp.float32)                # unaligned head_dim
    assert not pa.supports(ok[0], ok[1], jnp.int32)


def test_paged_prefill_matches_dense_forward(gpt):
    """One whole-prompt paged forward == the plain dense forward (the
    foundation of the engine's token parity)."""
    import jax.numpy as jnp
    from paddle_tpu.tensor import Tensor
    m = gpt
    m.eval()
    ids = pt.randint(0, 64, [1, 6])
    with pt.no_grad():
        full = m(ids).numpy()
        pool = BlockPool.for_model(m, num_blocks=8, block_size=4)
        table = np.zeros((1, 2), np.int32)
        table[0] = [3, 5]
        caches = [{"k": Tensor._from_array(pool.planes["k"][i]),
                   "v": Tensor._from_array(pool.planes["v"][i]),
                   "table": Tensor._from_array(jnp.asarray(table)),
                   "pos": Tensor._from_array(jnp.zeros(1, jnp.int32)),
                   "limit": Tensor._from_array(
                       jnp.full((1,), 6, jnp.int32))}
                  for i in range(pool.num_layers)]
        paged = m(ids, caches=caches).numpy()
    np.testing.assert_allclose(paged, full, rtol=2e-4, atol=2e-5)


# ===================================================================
# generate(): per-sequence EOS stop in a batch (serving-reuse fix)
# ===================================================================
def test_generate_batch_eos_per_sequence():
    m = _tiny_gpt()
    a = [1, 2, 3, 4, 5]
    b = [9, 8, 7, 6, 5]
    # pick an eos the FIRST row emits early but the second does not
    eos = _seq_ref(m, a, 1)[0]
    solo_b = _seq_ref(m, b, 6, eos=eos)
    batch = generate(m, pt.to_tensor(np.asarray([a, b], "int64")),
                     max_new_tokens=6, eos_token_id=eos).numpy()
    gen_a, gen_b = batch[0, 5:].tolist(), batch[1, 5:].tolist()
    # the finished row is eos-padded right of its stop, not garbage...
    assert all(t == eos for t in gen_a[gen_a.index(eos):])
    # ...and the unfinished row decodes exactly its solo trajectory
    assert gen_b[:len(solo_b)] == solo_b


# ===================================================================
# AOT artifacts: zero-compile warm replica start
# ===================================================================
def test_serving_aot_roundtrip_zero_compile(gpt, tmp_path):
    import json
    prompts = [[1, 2, 3, 4, 5], [7] * 11]
    kw = dict(num_blocks=16, block_size=8, max_running=4,
              prefill_chunk=16)
    eng = LLMEngine(gpt, **kw)
    refs = eng.generate_batch(prompts, max_new_tokens=5)
    export_serving_artifacts(eng, str(tmp_path),
                             prompt_lens=[len(p) for p in prompts])

    warm = LLMEngine(gpt, **kw)
    keys = load_serving_artifacts(warm, str(tmp_path))
    assert ("decode",) in keys
    assert warm.generate_batch(prompts, max_new_tokens=5) == refs
    # the warm replica never traced/compiled a live program
    assert warm._programs == {}

    # a stamp mismatch must refuse WITH the reason (strict=True raises)
    man = os.path.join(str(tmp_path), "serving_manifest.json")
    with open(man) as f:
        data = json.load(f)
    data["stamp"]["jax"] = "0.0.0-somewhere-else"
    with open(man, "w") as f:
        json.dump(data, f)
    cold = LLMEngine(gpt, **kw)
    with pytest.warns(UserWarning, match="jax version"):
        assert load_serving_artifacts(cold, str(tmp_path)) == []
    from paddle_tpu.jit.save_load import AOTIncompatible
    with pytest.raises(AOTIncompatible):
        load_serving_artifacts(cold, str(tmp_path), strict=True)


@pytest.mark.parametrize("older", [None, 2], ids=["layout-1", "layout-2"])
def test_serving_aot_of_another_program_layout_is_refused(gpt, tmp_path,
                                                          older):
    """An artifact exported before the decode program returned its ids
    (a manifest without `layout`: the same arguments, other outputs), or
    before it took `prev_ids` and `src` (layout 2: other arguments), is
    refused with the reason, and the live programs serve."""
    import json
    prompts = [[1, 2, 3, 4, 5], [7] * 11]
    kw = dict(num_blocks=16, block_size=8, max_running=4,
              prefill_chunk=16)
    eng = LLMEngine(gpt, **kw)
    refs = eng.generate_batch(prompts, max_new_tokens=5)
    assert export_serving_artifacts(eng, str(tmp_path))["layout"] == 3
    man = os.path.join(str(tmp_path), "serving_manifest.json")
    with open(man) as f:
        data = json.load(f)
    if older is None:
        del data["layout"]
    else:
        data["layout"] = older
    with open(man, "w") as f:
        json.dump(data, f)
    cold = LLMEngine(gpt, **kw)
    with pytest.warns(UserWarning, match="program layout mismatch"):
        assert load_serving_artifacts(cold, str(tmp_path)) == []
    assert cold.generate_batch(prompts, max_new_tokens=5) == refs
    assert ("decode",) in cold._programs
    from paddle_tpu.jit.save_load import AOTIncompatible
    with pytest.raises(AOTIncompatible, match=f"layout {older or 1}"):
        load_serving_artifacts(cold, str(tmp_path), strict=True)


# ===================================================================
# chaos sites + the overload drill (tier-1 wiring of --serving)
# ===================================================================
def test_pool_exhausted_chaos_site():
    from paddle_tpu.resilience import chaos
    pool = BlockPool(1, 8, 4, {"k": (2, 8), "v": (2, 8)})
    with chaos.scoped("serving.pool_exhausted@1"):
        assert pool.allocate(1) is None     # injected refusal
        a = pool.allocate(1)                # next hit is clean
        assert len(a) == 1
    pool.free(a)


def test_chaos_check_serving_inprocess():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chaos_check", os.path.join(REPO, "tools", "chaos_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    buf = io.StringIO()
    assert mod.run_serving(out=buf) == 0, buf.getvalue()
    assert "zero block leaks" in buf.getvalue()


# ===================================================================
# request validation
# ===================================================================
def test_add_request_validation(gpt):
    eng = LLMEngine(gpt, num_blocks=4, block_size=4)   # 16 token pool
    with pytest.raises(ValueError):
        eng.add_request([], max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.add_request([1] * 60, max_new_tokens=10)  # > max_model_len
    with pytest.raises(PoolExhausted):
        eng.add_request([1] * 20, max_new_tokens=10)  # > whole pool
