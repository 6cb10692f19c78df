"""Serving-tier survival: the multi-replica router + the engine's
graceful-degradation layer (paddle_tpu/serving/router.py + engine
deadlines/shedding/starvation guard).

The load-bearing properties:

* routing/failover may never change a token — a request served across
  a replica death finishes byte-identical to the sequential reference;
* overload degrades to FAST structured refusals (ShedRequest with a
  reason + the gauge values), never unbounded queue growth — the
  admitted requests' queue depth stays under the watermark throughout;
* every abnormal exit (deadline expiry, drain, shed, failover, replica
  death) frees all resources — pools come back with zero leaked blocks;
* hang (stale heartbeat) and crash (raise/exit) are DISTINCT eviction
  causes.

Tier-1 wiring of ``chaos_check --router`` lives here too.
"""
import io
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.launch.heartbeat import BeatWatch
from paddle_tpu.observability import metrics
from paddle_tpu.serving import LLMEngine, Router, ShedRequest
from paddle_tpu.text import GPTConfig, GPTForCausalLM
from paddle_tpu.text.generation import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gpt():
    pt.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    return GPTForCausalLM(cfg)


def _seq_ref(model, prompt, n, eos=None):
    out = generate(model, pt.to_tensor(np.asarray([prompt], "int64")),
                   max_new_tokens=n, eos_token_id=eos)
    return out.numpy()[0, len(prompt):].tolist()


def _factory(gpt, **overrides):
    kw = dict(num_blocks=24, block_size=4, max_running=8,
              prefill_chunk=16)
    kw.update(overrides)
    return lambda: LLMEngine(gpt, **kw)


# ===================================================================
# routing: least-loaded spread, session affinity
# ===================================================================
def test_router_least_loaded_spread_parity(gpt):
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=n).tolist()
               for n in (5, 9, 4, 11, 7, 6)]
    refs = [_seq_ref(gpt, p, 6) for p in prompts]
    router = Router(_factory(gpt), replicas=2, heartbeat_timeout=30.0)
    rrs = [router.submit(p, max_new_tokens=6) for p in prompts]
    router.run()
    assert [rr.emitted for rr in rrs] == refs
    # least-loaded admission actually spread the work
    assert {rr.replica_names[0] for rr in rrs} == {"r0", "r1"}
    leaks = router.close()
    assert all(leaked == [] and bad == []
               for leaked, bad in leaks.values())


def test_router_session_affinity(gpt):
    reg = metrics.registry()
    base = reg.counter("router_affinity_hits_total").value
    router = Router(_factory(gpt), replicas=3, heartbeat_timeout=30.0)
    rrs = [router.submit([1, 2, 3, 4], max_new_tokens=4,
                         session_id="conv-1") for _ in range(3)]
    assert len({rr.replica_names[0] for rr in rrs}) == 1
    assert reg.counter("router_affinity_hits_total").value - base == 2
    # a different session is free to land elsewhere (no pinning leak)
    other = router.submit([5, 6, 7], max_new_tokens=4, session_id="c2")
    router.run()
    assert other.state == "finished"
    router.close()


# ===================================================================
# load shedding: structured refusals, bounded queue (the acceptance
# criterion: overload keeps admitted TTFT bounded, shed requests get a
# structured refusal and free all resources)
# ===================================================================
def test_engine_shed_queue_depth_watermark(gpt):
    reg = metrics.registry()
    base = reg.counter("serving_requests_shed_total",
                       reason="queue_depth").value
    eng = _factory(gpt, num_blocks=6, max_running=1,
                   shed_queue_depth=2)()
    admitted, shed = [], []
    for i in range(8):
        try:
            admitted.append(eng.add_request([1 + i] * 5,
                                            max_new_tokens=4))
        except ShedRequest as e:
            shed.append(e)
    # no step() has run yet, so nothing moved queue->running: the
    # queue takes `watermark` requests and every later submit sheds
    assert len(shed) == 6
    for e in shed:
        assert e.reason == "queue_depth"
        assert e.detail["queue_depth"] >= 2
        assert e.detail["watermark"] == 2
    assert reg.counter("serving_requests_shed_total",
                       reason="queue_depth").value - base == 6
    # the queue NEVER grows past the watermark while the backlog drains
    while eng.has_work:
        assert eng.scheduler.queue_depth <= 2
        eng.step()
    assert all(r.finish_reason == "length" for r in admitted)
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_engine_shed_free_blocks_watermark(gpt):
    eng = _factory(gpt, num_blocks=4, max_running=1,
                   shed_free_blocks=2)()
    a = eng.add_request([1] * 9, max_new_tokens=4)   # takes 3 blocks
    eng.step()
    b = eng.add_request([2] * 9, max_new_tokens=4)   # queues (no slot)
    with pytest.raises(ShedRequest) as ei:
        eng.add_request([3] * 9, max_new_tokens=4)
    assert ei.value.reason == "free_blocks"
    assert ei.value.detail["free_blocks"] < 2
    eng.run()
    assert a.finish_reason == "length" and b.finish_reason == "length"
    assert eng.pool.check_leaks() == ([], [])


def test_router_sheds_when_every_replica_refuses(gpt):
    router = Router(_factory(gpt, max_running=1, shed_queue_depth=1),
                    replicas=2, heartbeat_timeout=30.0)
    ok = []
    with pytest.raises(ShedRequest) as ei:
        for i in range(8):
            ok.append(router.submit([1 + i] * 4, max_new_tokens=4))
    assert ei.value.reason == "queue_depth"
    assert ei.value.detail["replicas_tried"] == 2
    # no steps ran between submissions: each replica's queue holds the
    # watermark's worth, then the ROUTER sheds (both replicas refused)
    assert len(ok) == 2
    router.run()
    assert all(rr.state == "finished" for rr in ok)
    router.close()


# ===================================================================
# deadlines: queue-wait and TTL expiry are clean finishes
# ===================================================================
def test_queue_deadline_expires_cleanly(gpt):
    reg = metrics.registry()
    base = reg.counter("serving_requests_expired_total",
                       where="queue").value
    eng = _factory(gpt, num_blocks=4, max_running=1)()
    done = []
    a = eng.add_request([1] * 9, max_new_tokens=6)      # hogs the slot
    b = eng.add_request([2] * 9, max_new_tokens=6,      # waits
                        queue_deadline_s=0.05,
                        on_finish=lambda r: done.append(r.id))
    t0 = time.monotonic()
    while eng.has_work and time.monotonic() - t0 < 30:
        eng.step()
    assert a.finish_reason == "length"
    assert b.finish_reason == "expired-queue"
    assert b.state == "expired"
    assert done == [b.id]
    assert reg.counter("serving_requests_expired_total",
                       where="queue").value - base == 1
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_ttl_expires_running_request_and_frees_blocks(gpt):
    reg = metrics.registry()
    base = reg.counter("serving_requests_expired_total",
                       where="ttl").value
    eng = _factory(gpt)()
    a = eng.add_request([1, 2, 3], max_new_tokens=50, ttl_s=0.02)
    b = eng.add_request([4, 5, 6], max_new_tokens=4)
    t0 = time.monotonic()
    while eng.has_work and time.monotonic() - t0 < 30:
        eng.step()
    assert a.finish_reason == "expired-ttl"
    assert len(a.generated) < 50            # cut off mid-generation
    assert b.finish_reason == "length"      # neighbors unaffected
    assert reg.counter("serving_requests_expired_total",
                       where="ttl").value - base == 1
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == eng.pool.num_blocks


# ===================================================================
# failover building blocks: resume_tokens, cancel
# ===================================================================
def test_resume_tokens_continuation_parity(gpt):
    prompt = [7, 3, 9, 1, 5]
    ref = _seq_ref(gpt, prompt, 8)
    eng = _factory(gpt)()
    req = eng.add_request(prompt, max_new_tokens=8,
                          resume_tokens=ref[:3])
    eng.run()
    # the resumed request re-prefills prompt+resume and continues at
    # token 3 — the full stream is byte-identical to never moving
    assert req.generated == ref
    assert req.resumed


def test_resume_tokens_sampled_parity(gpt):
    """Per-(seed, position) sampling makes even SAMPLED streams
    resume-exact: the survivor re-derives the same draws."""
    prompt = [11, 4, 2, 8]
    kw = dict(max_new_tokens=8, do_sample=True, temperature=0.9,
              top_k=20, seed=42)
    eng = _factory(gpt)()
    full = eng.add_request(prompt, **kw)
    eng.run()
    resumed = eng.add_request(prompt, resume_tokens=full.generated[:4],
                              **kw)
    eng.run()
    assert resumed.generated == full.generated


def test_resume_tokens_validation(gpt):
    eng = _factory(gpt)()
    with pytest.raises(ValueError, match="nothing left"):
        eng.add_request([1, 2, 3], max_new_tokens=4,
                        resume_tokens=[5, 6, 7, 8])


def test_engine_cancel_frees_blocks(gpt):
    eng = _factory(gpt)()
    req = eng.add_request([1] * 6, max_new_tokens=50)
    eng.step()
    eng.step()
    assert req.block_table        # running, holding blocks
    eng.cancel(req)
    assert req.finish_reason == "cancelled"
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == eng.pool.num_blocks
    eng.cancel(req)               # idempotent on settled requests


# ===================================================================
# starvation guard: repeated skips promote out of the victim pool
# ===================================================================
def test_starvation_promotion_counter_and_completion(gpt):
    reg = metrics.registry()
    base = reg.counter("serving_starvation_promotions_total").value
    prompts = [[1 + i] * 9 for i in range(3)]
    refs = [_seq_ref(gpt, p, 8) for p in prompts]
    # 6 blocks of 4 for three 17-token requests: sustained block
    # pressure -> repeated LIFO preemption; aging must promote rather
    # than livelock, and promotion may never change a token
    eng = _factory(gpt, num_blocks=6, max_running=3, promote_after=2)()
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    eng.run(max_steps=10_000)
    assert [r.generated for r in reqs] == refs
    assert reg.counter(
        "serving_starvation_promotions_total").value - base >= 1
    assert any(r.promoted for r in reqs)
    assert eng.pool.check_leaks() == ([], [])


# ===================================================================
# graceful shutdown: drain + close
# ===================================================================
def test_engine_drain_and_close(gpt):
    eng = _factory(gpt, max_running=2)()
    running = [eng.add_request([1 + i] * 5, max_new_tokens=4)
               for i in range(2)]
    eng.step()
    queued = eng.add_request([9] * 5, max_new_tokens=4)
    eng.scheduler.max_running = 2   # keep it queued
    summary = eng.drain(ttl_s=30.0)
    # draining: queued work expired immediately, running finished
    assert queued.finish_reason == "drained"
    assert all(r.finish_reason == "length" for r in running)
    assert summary["drained"] >= 1
    with pytest.raises(ShedRequest) as ei:
        eng.add_request([1, 2], max_new_tokens=2)
    assert ei.value.reason == "draining"
    leaks = eng.close()
    assert leaks == ([], [])
    assert eng.pool.planes == {}
    with pytest.raises(RuntimeError, match="closed"):
        eng.add_request([1, 2], max_new_tokens=2)


def test_engine_drain_ttl_expires_running(gpt):
    eng = _factory(gpt)()
    req = eng.add_request([1] * 5, max_new_tokens=50)
    eng.step()
    eng.drain(ttl_s=0.0)          # budget exhausted immediately
    assert req.finish_reason == "drained"
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_router_drain_sheds_new_work(gpt):
    router = Router(_factory(gpt), replicas=2, heartbeat_timeout=30.0)
    rr = router.submit([1, 2, 3, 4], max_new_tokens=4)
    router.drain(ttl_s=30.0)
    assert rr.state == "finished"
    with pytest.raises(ShedRequest) as ei:
        router.submit([5, 6], max_new_tokens=2)
    assert ei.value.reason == "draining"
    router.close()


def test_client_callback_error_fails_only_that_request(gpt):
    """A broken client stream (on_token raises) must fail ITS request —
    never propagate into engine.step where the router would misread it
    as a replica crash and evict a healthy replica."""
    router = Router(_factory(gpt), replicas=2, heartbeat_timeout=30.0)

    def broken(rr, tok):
        raise BrokenPipeError("client went away")

    good_prompt = [2, 4, 6, 8]
    ref = _seq_ref(gpt, good_prompt, 5)
    bad_rr = router.submit([1, 3, 5], max_new_tokens=5, on_token=broken)
    ok_rr = router.submit(good_prompt, max_new_tokens=5)
    with pytest.warns(UserWarning, match="client callback"):
        router.run()
    assert bad_rr.state == "failed"
    assert bad_rr.finish_reason == "client_error"
    assert ok_rr.state == "finished" and ok_rr.emitted == ref
    # no eviction happened for a client-side failure
    assert [s.state for s in router._slots] == ["healthy", "healthy"]
    assert router.events == []
    leaks = router.close()
    assert all(leaked == [] and bad == []
               for leaked, bad in leaks.values())


# ===================================================================
# heartbeat: BeatWatch staleness semantics (watcher-clock based)
# ===================================================================
def test_beatwatch_staleness(tmp_path):
    clock = {"t": 100.0}
    path = str(tmp_path / "hb")
    w = BeatWatch(path, timeout=5.0, clock=lambda: clock["t"])
    # missing file: grace period, then stale
    assert not w.stale()
    clock["t"] += 6.0
    assert w.stale()
    # a beat (mtime change) resets the window
    with open(path, "w"):
        pass
    assert not w.stale()
    clock["t"] += 4.0
    assert not w.stale()          # within timeout
    clock["t"] += 2.0
    assert w.stale()              # silent past timeout
    os.utime(path, (1, 12345))    # fresh beat observed -> alive again
    assert not w.stale()
    assert w.silent_for == 0.0


# ===================================================================
# tier-1 wiring of the survival drill
# ===================================================================
def test_chaos_check_router_inprocess():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chaos_check_router", os.path.join(REPO, "tools",
                                           "chaos_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    buf = io.StringIO()
    assert mod.run_router(out=buf) == 0, buf.getvalue()
    out = buf.getvalue()
    assert "crash-loop abandon" in out
    assert "stale heartbeat" in out
