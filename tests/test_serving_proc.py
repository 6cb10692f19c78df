"""Process-per-replica serving: the framed transport, the worker
process lifecycle, and the kill -9 survival drill.

Load-bearing properties:

* **framing is structural** — frames survive arbitrary wire splits
  (seeded random split points), while torn final frames, oversized
  frames and garbage payloads are REJECTED (FrameError), never
  silently skipped: a dropped frame must become an eviction+failover,
  not a token gap;
* **the transport cannot wedge the router** — blocking reads run under
  the PR-6-shaped TransportPolicy (timeout x retries x backoff), every
  expired attempt counted;
* **cross-process parity** — a stream served by a worker PROCESS
  (including a failover-style ``resume_tokens`` continuation, greedy
  AND sampled) is byte-identical to the in-process engine and the
  sequential reference;
* **no orphans** — close() reports leaks over the wire then reaps;
  abort() TERM→KILLs even a worker that ignores SIGTERM (the wedged-
  in-native-code case).

Tier-1 wiring of ``chaos_check --router --proc`` (real SIGKILL drill)
lives here too, under a wall-clock budget guard.
"""
import io
import os
import signal
import socket
import struct
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.launch.heartbeat import BeatWatch
from paddle_tpu.observability import metrics
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import ShedRequest
from paddle_tpu.serving import worker as sw
from paddle_tpu.serving.transport import (MAX_FRAME, Channel,
                                          ChannelClosed, FrameDecoder,
                                          FrameError, TransportPolicy,
                                          TransportTimeout, encode)
from paddle_tpu.text import GPTConfig, GPTForCausalLM
from paddle_tpu.text.generation import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG_KW = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
              max_position_embeddings=64, hidden_dropout=0.0,
              attention_dropout=0.0, tensor_parallel=False)
ENG_KW = dict(num_blocks=24, block_size=4, max_running=8,
              prefill_chunk=16)


# ===================================================================
# framing: property tests over the pure decoder (no sockets)
# ===================================================================
def _sample_messages(rng, n=40):
    """A realistic interleaving: stream events, step summaries, and a
    few replies mixed in (replies interleave with events on the real
    wire, and order must survive)."""
    out = []
    for i in range(n):
        k = rng.randint(4)
        if k == 0:
            out.append({"ev": "tok", "rid": int(rng.randint(8)),
                        "tok": int(rng.randint(50304))})
        elif k == 1:
            out.append({"ev": "fin", "rid": int(rng.randint(8)),
                        "reason": "eos"})
        elif k == 2:
            out.append({"ev": "step",
                        "summary": {"decoded": int(rng.randint(8)),
                                    "admitted": 0},
                        "gauges": [int(rng.randint(9)), 0, 24]})
        else:
            out.append({"reply": "add_request", "rid": i, "ok": True,
                        "gauges": [0, 1, 23]})
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_framing_roundtrip_random_split_points(seed):
    rng = np.random.RandomState(seed)
    msgs = _sample_messages(rng)
    blob = b"".join(encode(m) for m in msgs)
    dec = FrameDecoder()
    got = []
    i = 0
    while i < len(blob):
        j = i + int(rng.randint(1, 9))   # partial reads, torn anywhere
        got.extend(dec.feed(blob[i:j]))
        i = j
    assert got == msgs
    dec.close()                          # clean EOF at a frame boundary
    assert dec.pending == 0


def test_framing_torn_final_frame_rejected():
    msgs = _sample_messages(np.random.RandomState(7), n=5)
    blob = b"".join(encode(m) for m in msgs)
    dec = FrameDecoder()
    got = dec.feed(blob[:-3])            # EOF lands mid-final-frame
    assert got == msgs[:-1]
    with pytest.raises(FrameError, match="torn"):
        dec.close()


def test_framing_oversized_frame_rejected_both_sides():
    dec = FrameDecoder(max_frame=64)
    with pytest.raises(FrameError, match="oversized"):
        dec.feed(struct.pack("!I", 65))  # header alone convicts it
    with pytest.raises(FrameError, match="too large"):
        encode({"pad": "x" * 128}, max_frame=64)
    # default bound is sane
    assert MAX_FRAME >= 1 << 20


def test_framing_garbage_payload_rejected():
    dec = FrameDecoder()
    with pytest.raises(FrameError, match="undecodable"):
        dec.feed(struct.pack("!I", 4) + b"\xff\xfe\x00\x01")


def test_channel_preserves_event_reply_interleaving():
    a, b = socket.socketpair()
    parent, worker = Channel(a, "parent"), Channel(b, "worker")
    seq = [{"ev": "tok", "rid": 0, "tok": 1},
           {"reply": "add_request", "rid": 1, "ok": True},
           {"ev": "tok", "rid": 0, "tok": 2},
           {"ev": "fin", "rid": 0, "reason": "length"}]
    for m in seq:
        worker.send(m)
    got = [parent.recv(timeout=5.0) for _ in seq]
    assert got == seq
    assert parent.poll() is None         # drained, no EOF yet
    worker.close()
    with pytest.raises(ChannelClosed):
        parent.recv(timeout=5.0)
    parent.close()


def test_channel_chaos_transport_drop_site():
    a, b = socket.socketpair()
    parent, worker = Channel(a, "r9"), Channel(b, "w")
    for i in range(3):
        worker.send({"ev": "tok", "rid": 0, "tok": i})
    with chaos.scoped("serving.transport_drop@2#r9"):
        assert parent.poll() == {"ev": "tok", "rid": 0, "tok": 0}
        with pytest.raises(FrameError, match="transport_drop"):
            parent.poll()                # frame 2 dropped in transit
    parent.close()
    worker.close()


# ===================================================================
# transport policy: a silent peer costs timeouts, never a wedge
# ===================================================================
class _SilentProc:
    """A 'worker' that is alive but never answers."""
    pid = 0

    @staticmethod
    def poll():
        return None


def test_rpc_timeout_policy_counts_and_raises():
    reg = metrics.registry()
    base = reg.counter("router_transport_timeouts_total").value
    a, b = socket.socketpair()
    pr = object.__new__(sw.ProcReplica)
    pr.name = "silent"
    pr.ch = Channel(a, "silent")
    pr.proc = _SilentProc()
    pr.policy = TransportPolicy(timeout=0.05, retries=1,
                                backoff_base=0.0)
    pr._pending_reply = None
    pr._reqs = {}
    pr._gauges = (0, 0, 0)
    pr._summary = None
    pr._exit_noted = False
    t0 = time.monotonic()
    with pytest.raises(TransportTimeout, match="no reply"):
        pr._rpc("metrics_snapshot")
    # two attempts (timeout x (retries+1)), each counted; and the wait
    # actually returned instead of wedging
    assert reg.counter("router_transport_timeouts_total").value \
        - base == 2
    assert time.monotonic() - t0 < 5.0
    pr.ch.close()
    b.close()


def test_raise_remote_rebuilds_structured_shed():
    with pytest.raises(ShedRequest) as ei:
        sw._raise_remote({"kind": "ShedRequest", "reason": "queue_depth",
                          "detail": {"queue_depth": 5, "watermark": 2}})
    assert ei.value.reason == "queue_depth"
    assert ei.value.detail["queue_depth"] == 5
    with pytest.raises(ValueError, match="nothing left"):
        sw._raise_remote({"kind": "ValueError",
                          "message": "nothing left to generate"})


# ===================================================================
# BeatWatch spawn grace: a worker importing/compiling for longer than
# the heartbeat timeout must not be evicted before its FIRST beat
# ===================================================================
def test_beatwatch_spawn_grace(tmp_path):
    clock = {"t": 100.0}
    path = str(tmp_path / "hb")
    w = BeatWatch(path, timeout=5.0, grace=30.0,
                  clock=lambda: clock["t"])
    # missing file: past the plain timeout but inside the grace window
    clock["t"] += 20.0
    assert not w.stale()
    # grace exhausted without a single beat: genuinely hung startup
    clock["t"] += 11.0
    assert w.stale()
    # first beat observed -> grace disarms, plain timeout from then on
    with open(path, "w"):
        pass
    assert not w.stale()
    clock["t"] += 6.0
    assert w.stale()                 # 6s silence > 5s timeout: no more
    #                                  grace once the worker has beaten
    # default grace is the timeout itself (in-process behavior intact)
    w2 = BeatWatch(str(tmp_path / "hb2"), timeout=5.0,
                   clock=lambda: clock["t"])
    assert w2.grace == 5.0


def test_beatwatch_respawn_leftover_file_keeps_grace(tmp_path):
    """A RESPAWNED slot reuses its hb path — the dead predecessor's
    leftover file is the fresh watch's baseline, NOT a beat, so the
    new worker still gets the full grace window before its first
    beat (the regression: leftover mtime disarmed grace, and a slow
    respawn was hang-evicted into the crash-loop detector)."""
    clock = {"t": 50.0}
    path = str(tmp_path / "hb")
    with open(path, "w"):
        pass                       # the dead worker's leftover beat
    w = BeatWatch(path, timeout=5.0, grace=30.0,
                  clock=lambda: clock["t"])
    clock["t"] += 20.0             # past timeout, inside grace — the
    assert not w.stale()           # leftover file must not count
    os.utime(path, (1, 99999))     # the NEW worker's first real beat
    assert not w.stale()
    clock["t"] += 6.0              # grace disarmed only now
    assert w.stale()


# ===================================================================
# cross-process parity (one worker serves all the parity cases)
# ===================================================================
@pytest.fixture(scope="module")
def gpt():
    pt.seed(0)
    return GPTForCausalLM(GPTConfig(**CFG_KW))


@pytest.fixture(scope="module")
def proc_replica(tmp_path_factory):
    spec = sw.gpt_spec(config=CFG_KW, seed=0, engine=ENG_KW)
    hb = str(tmp_path_factory.mktemp("hb") / "hb.w0")
    h = sw.ProcReplica(spec, "w0", hb,
                       policy=TransportPolicy(timeout=120.0, retries=0))
    assert h.wait_ready(timeout=300.0)
    yield h
    h.abort()        # safety net; the close test already reaped it


def _seq_ref(model, prompt, n):
    out = generate(model, pt.to_tensor(np.asarray([prompt], "int64")),
                   max_new_tokens=n)
    return out.numpy()[0, len(prompt):].tolist()


def _drive(handle, *reqs, budget_s=120.0):
    t0 = time.monotonic()
    while any(r.finish_reason is None for r in reqs):
        assert time.monotonic() - t0 < budget_s, "worker stalled"
        handle.step()
        time.sleep(0.002)


def test_cross_process_greedy_and_resume_parity(gpt, proc_replica):
    prompt = [7, 3, 9, 1, 5]
    ref = _seq_ref(gpt, prompt, 8)
    toks = []
    rq = proc_replica.add_request(
        prompt, max_new_tokens=8,
        on_token=lambda r, t: toks.append(t))
    _drive(proc_replica, rq)
    assert rq.generated == ref == toks
    assert rq.finish_reason == "length"
    # failover-style continuation: seed half the stream, the worker
    # re-prefills and continues — `generated` holds the ABSOLUTE stream
    rq2 = proc_replica.add_request(prompt, max_new_tokens=8,
                                   resume_tokens=ref[:3])
    _drive(proc_replica, rq2)
    assert rq2.generated == ref


def test_cross_process_sampled_resume_parity(gpt, proc_replica):
    from paddle_tpu.serving import LLMEngine
    prompt = [11, 4, 2, 8]
    kw = dict(max_new_tokens=8, do_sample=True, temperature=0.9,
              top_k=20, seed=42)
    # in-process reference on weight-identical model (same seed/config)
    eng = LLMEngine(gpt, **ENG_KW)
    local = eng.add_request(prompt, **kw)
    eng.run()
    rq = proc_replica.add_request(prompt, **kw)
    _drive(proc_replica, rq)
    assert rq.generated == local.generated
    # resume-exactness survives the process boundary: per-(seed,
    # position) draws re-derive the same stream
    rq2 = proc_replica.add_request(prompt,
                                   resume_tokens=local.generated[:4],
                                   **kw)
    _drive(proc_replica, rq2)
    assert rq2.generated == local.generated
    eng.close()


def test_cross_process_validation_error_rebuilt(proc_replica):
    with pytest.raises(ValueError, match="nothing left"):
        proc_replica.add_request([1, 2, 3], max_new_tokens=4,
                                 resume_tokens=[5, 6, 7, 8])


def test_worker_metrics_snapshot_rpc(proc_replica):
    snap = proc_replica.metrics_snapshot()
    names = {rec["name"] for rec in snap}
    assert "serving_tokens_generated_total" in names
    tok = sum(rec.get("value", 0) for rec in snap
              if rec["name"] == "serving_tokens_generated_total")
    assert tok >= 8       # the parity streams above ran in THIS worker


def test_worker_close_reports_leaks_and_reaps(proc_replica):
    pid = proc_replica.proc.pid
    leaks = proc_replica.close()
    assert leaks == ([], [])          # leak report crossed the wire
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)               # dead AND reaped — no orphan


def test_wedged_worker_needs_kill_escalation(tmp_path):
    """A worker stuck in native code ignores SIGTERM; abort() must
    escalate to SIGKILL and still reap — the hang-eviction teardown."""
    spec = sw.gpt_spec(config=CFG_KW, seed=0, engine=ENG_KW)
    h = sw.ProcReplica(spec, "wedge", str(tmp_path / "hb"),
                       policy=TransportPolicy(timeout=120.0, retries=0))
    assert h.wait_ready(timeout=300.0)
    pid = h.proc.pid
    h.ch.send({"cmd": "_wedge"})      # stops beating/reading, TERM-proof
    time.sleep(0.5)                   # let it enter the wedge
    h.abort()
    assert h.proc.poll() is not None
    assert h.proc.returncode == -signal.SIGKILL   # TERM was not enough
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)


# ===================================================================
# tier-1 wiring of the kill -9 drill, under a wall-clock budget
# ===================================================================
def test_chaos_check_router_proc_drill():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chaos_check_proc", os.path.join(REPO, "tools",
                                         "chaos_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    buf = io.StringIO()
    t0 = time.monotonic()
    assert mod.run_router_proc(out=buf) == 0, buf.getvalue()
    elapsed = time.monotonic() - t0
    out = buf.getvalue()
    assert "kill -9'd 3x" in out
    assert "zero orphaned workers" in out
    # budget guard: the subprocess drill must fit tier-1's 870 s
    # timeout with plenty of room for the rest of the suite (the drill
    # itself re-checks PROC_BUDGET_S internally)
    assert elapsed < mod.PROC_BUDGET_S, (
        f"proc drill took {elapsed:.0f}s — too slow for tier-1")


# ===================================================================
# one process per chip: the --proc parent leaves the device to workers
# ===================================================================
def test_serve_proc_parent_initialises_no_backend():
    """`tools/serve.py --proc`: a chip belongs to one process at a time,
    so the parent must not build a model, seed, or otherwise initialise
    a jax backend before or while its workers live.  Asserted INSIDE
    the spawned parent, at the moment the tier is closed (the workers
    are still running then) and again after it returned."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        sys.path.insert(0, {os.path.join(REPO, "tools")!r})
        import serve
        from jax._src import xla_bridge
        from paddle_tpu import serving

        seen = []
        close = serving.Router.close

        def spying_close(self):
            alive = [s.handle.proc.poll() is None for s in self._slots
                     if s.handle is not None]
            seen.append((alive, sorted(xla_bridge._backends)))
            return close(self)

        serving.Router.close = spying_close
        rc = serve.main(["--proc", "--replicas", "1", "--random", "4",
                         "--max-new-tokens", "4", "-q"])
        assert rc == 0, rc
        assert seen == [([True], [])], seen
        assert not xla_bridge._backends, sorted(xla_bridge._backends)
        print("PARENT_HAS_NO_BACKEND")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PARENT_HAS_NO_BACKEND" in r.stdout
    assert r.stdout.count("DONE (length)") == 4


def test_check_proc_replicas_refuses_a_second_worker_on_a_chip(monkeypatch):
    from paddle_tpu import device
    monkeypatch.setattr(device, "tpu_chips_visible", lambda: 1)
    sw.check_proc_replicas(1)
    with pytest.raises(SystemExit, match="belongs to one process"):
        sw.check_proc_replicas(2)
    monkeypatch.setattr(device, "tpu_chips_visible", lambda: 0)
    sw.check_proc_replicas(4)          # no TPU here: CPU tiers are free


def test_rpc_wait_leaves_frames_behind_the_reply_buffered():
    """A fast worker streams a request's first token right behind its
    `add_request` reply.  The router binds the stream to the request
    only after the call returns, so the RPC wait must stop at the reply
    — dispatched inside the wait, that token was dropped as "stale"
    (seen as a 15-of-16-token stream in the kill -9 drill under load)."""
    frames = [{"reply": "add_request", "ok": True},
              {"ev": "tok", "rid": 0, "tok": 42}]

    class FakeChannel:
        def poll(self):
            return frames.pop(0) if frames else None

    h = sw.ProcReplica.__new__(sw.ProcReplica)
    h.ch, h._pending_reply = FakeChannel(), None
    seen = []
    h._reqs = {0: sw.RemoteRequest(
        0, on_token=lambda rq, tok: seen.append(tok))}
    h._pump(until_reply=True)
    assert h._pending_reply == {"reply": "add_request", "ok": True}
    assert seen == [] and len(frames) == 1      # the token still waits
    h._pump()
    assert seen == [42]
