"""Persistent compile cache: storage contract, concurrency, corruption,
degradation, and the shape-bucketing decode policy.

Covers: entry roundtrip + checksum validation, every corruption mode
(bit-flip, truncation, garbage) quarantining instead of crashing,
size-budgeted GC that never collects the just-published entry,
two PROCESSES racing on one cache dir converging without deadlock or
torn reads, unwritable-dir degradation to in-memory with exactly one
warning, digest sensitivity (shape/dtype/static args), the
FunctionCache miss->mem->hit flow, RecompileWarning dedup per
(fn, cause), and bucketed generation emitting tokens identical to the
unbucketed loop.
"""
import hashlib
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.framework.compat import normalize_cost_analysis
from paddle_tpu.jit import compile_cache as cc
from paddle_tpu.jit.compile_cache import (CacheUnavailableWarning,
                                          CompileCache, FunctionCache)
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.resilience import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_cache_state():
    """Each test configures its own cache; none leaks to the next."""
    reg = MetricsRegistry()
    obs.enable(reg)
    yield reg
    obs.disable()
    cc.reset()
    cc._drop_memo_unsafe()


def _digest(s):
    return hashlib.sha256(s.encode()).hexdigest()


# ===================================================================
# store level
# ===================================================================
def test_roundtrip_and_header(tmp_path):
    c = CompileCache(str(tmp_path))
    c.put(_digest("k"), b"\x01" * 1000, meta={"label": "t"})
    assert c.get(_digest("k")) == b"\x01" * 1000
    assert c.get(_digest("other")) is None
    # crash-safe publish leaves no temp litter
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


@pytest.mark.parametrize("mode", ["flip", "truncate", "garbage"])
def test_corrupt_entry_quarantined_not_crashed(tmp_path, mode):
    c = CompileCache(str(tmp_path))
    d = _digest("victim")
    c.put(d, b"payload-bytes" * 100)
    chaos.corrupt_cache_entry(str(tmp_path), mode=mode)
    with pytest.warns(CacheUnavailableWarning, match="quarantined"):
        assert c.get(d) is None          # miss, not an exception
    q = os.path.join(tmp_path, "quarantine")
    assert os.path.isdir(q) and len(os.listdir(q)) == 1
    # the damaged entry left the lookup namespace entirely
    assert c.get(d) is None
    assert cc.stats()["quarantined"] == 1


def test_gc_evicts_oldest_but_protects_fresh(tmp_path):
    c = CompileCache(str(tmp_path), max_bytes=3000)
    for i in range(5):
        c.put(_digest(f"e{i}"), bytes([i]) * 900)
        os.utime(c._path(_digest(f"e{i}")), (i, i))  # deterministic age
    # budget 3000 holds ~3 entries; the newest (protected) must survive
    assert c.get(_digest("e4")) is not None
    assert c.get(_digest("e0")) is None   # oldest evicted
    assert c.total_bytes() <= 3000 + 1024  # header overhead slack
    assert cc.stats()["evictions"] >= 1


def test_unwritable_dir_degrades_with_one_warning(tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("occupied")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c = cc.configure(str(blocker))      # path is a file -> unwritable
        c.put(_digest("m"), b"mem-only")
        assert c.get(_digest("m")) == b"mem-only"   # in-memory fallback
        c.put(_digest("m2"), b"more")
    degraded = [x for x in w if issubclass(x.category,
                                           CacheUnavailableWarning)]
    assert len(degraded) == 1, [str(x.message) for x in w]
    assert "in-memory-only" in str(degraded[0].message)
    assert cc.stats()["degraded"] == 1


def test_two_processes_race_without_deadlock_or_torn_reads(tmp_path):
    """Two workers hammer the same digests with different payload sizes;
    lock-free last-writer-wins must never deadlock, never publish a torn
    entry (a reader validating a mixed write would quarantine it), and
    leave only whole entries behind."""
    worker = textwrap.dedent(f"""
        import sys, hashlib
        sys.path.insert(0, {REPO!r})
        from paddle_tpu.jit.compile_cache import CompileCache
        c = CompileCache(sys.argv[1], max_bytes=1 << 30)
        payload = sys.argv[2].encode() * int(sys.argv[3])
        for i in range(250):
            d = hashlib.sha256(str(i % 7).encode()).hexdigest()
            c.put(d, payload, meta={{"writer": sys.argv[2]}})
            got = c.get(d)
            assert got is not None, "published entry vanished"
        print("OK", flush=True)
    """)
    procs = [subprocess.Popen(
        [sys.executable, "-c", worker, str(tmp_path), tag, size],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for tag, size in (("a", "400"), ("b", "90000"))]
    for p in procs:
        out, err = p.communicate(timeout=240)   # timeout == deadlock
        assert p.returncode == 0, err
        assert "OK" in out
    # every surviving entry validates end-to-end in a fresh reader
    reader = CompileCache(str(tmp_path))
    live = [n for n in os.listdir(tmp_path) if n.endswith(".ccx")]
    assert len(live) == 7
    for n in live:
        assert reader.get(n[:-len(".ccx")]) is not None
    assert not os.path.isdir(tmp_path / "quarantine"), \
        "a torn/mixed write was published"
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


# ===================================================================
# digests
# ===================================================================
def test_digest_sensitivity():
    import jax.numpy as jnp
    fc = FunctionCache("t", fingerprint=("src",))
    a = (jnp.ones((2, 3)),)
    assert fc.digest(a) == fc.digest((jnp.zeros((2, 3)),))  # values don't key
    assert fc.digest(a) != fc.digest((jnp.ones((2, 4)),))   # shape does
    assert fc.digest(a) != fc.digest((jnp.ones((2, 3), jnp.int32),))
    assert fc.digest(a) != fc.digest(a, static=("train",))
    fc2 = FunctionCache("t", fingerprint=("other-src",))
    assert fc.digest(a) != fc2.digest(a)                    # code identity


# ===================================================================
# FunctionCache end-to-end (non-donating program: safe to deserialize
# in-process — see the _MEMO comment for why donated ones are not)
# ===================================================================
def test_lookup_miss_mem_hit_flow(tmp_path):
    import jax
    cc.configure(str(tmp_path))
    jitted = jax.jit(lambda x: x * 2.0 + 1.0)
    args = (np.ones((4,), np.float32),)
    fc = FunctionCache("flow", fingerprint=("flow-src",))
    runner, outcome, _ = fc.lookup(jitted, args)
    assert outcome == "miss"
    np.testing.assert_allclose(np.asarray(runner(*args)), np.full(4, 3.0))
    _, outcome2, _ = fc.lookup(jitted, args)
    assert outcome2 == "mem"            # process-global memo
    # a different FunctionCache for the same program also memo-hits:
    # one live executable instance per program per process
    _, outcome3, _ = FunctionCache("flow", fingerprint=("flow-src",)
                                   ).lookup(jitted, args)
    assert outcome3 == "mem"
    # simulate a restarted process (memo gone, disk warm)
    cc._drop_memo_unsafe()
    runner4, outcome4, extra = FunctionCache(
        "flow", fingerprint=("flow-src",)).lookup(jitted, args)
    assert outcome4 == "hit"
    np.testing.assert_allclose(np.asarray(runner4(*args)),
                               np.full(4, 3.0))
    s = cc.stats()
    assert s["misses"] == 1 and s["puts"] == 1


def test_extra_metadata_roundtrips_through_store(tmp_path):
    import jax
    cc.configure(str(tmp_path))
    jitted = jax.jit(lambda x: x + 1)
    args = (np.zeros((2,), np.float32),)
    fc = FunctionCache("extra", fingerprint=())
    _, outcome, _ = fc.lookup(jitted, args,
                              extra_fn=lambda: {"treedef": "leaf", "n": 1})
    assert outcome == "miss"
    cc._drop_memo_unsafe()
    _, outcome2, extra = FunctionCache("extra", fingerprint=()).lookup(
        jitted, args)
    if outcome2 == "hit":
        assert extra == {"treedef": "leaf", "n": 1}


# ===================================================================
# compile tracker: RecompileWarning dedup per (fn, cause)
# ===================================================================
def test_recompile_warning_once_per_cause(_clean_cache_state):
    from paddle_tpu.observability import compile_tracker as ct
    obs.enable(_clean_cache_state, warn_after=1)
    owner = object()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for n in (4, 5, 6, 7):          # a decode loop: new length each call
            tok = ct.on_call("decode_step",
                             ct.signature_of([np.ones((1, n))]),
                             owner=owner)
            ct.finish(tok)
    recs = [x for x in w if "recompilation dominates" in str(x.message)]
    assert len(recs) == 1, [str(x.message) for x in recs]


# ===================================================================
# shape bucketing
# ===================================================================
def test_bucket_policy_ladder_and_spec():
    from paddle_tpu.text.generation import BucketPolicy
    p = BucketPolicy()
    assert p.bucket(1) == 32 and p.bucket(32) == 32
    assert p.bucket(33) == 64 and p.bucket(200) == 256
    e = BucketPolicy(buckets=[64, 128, 512])
    assert e.bucket(10) == 64 and e.bucket(128) == 128
    assert e.bucket(513) == 1024        # doubles past the last bucket
    assert BucketPolicy.from_spec("off") is None
    assert BucketPolicy.from_spec(None) is None
    assert BucketPolicy.from_spec("on").min_bucket == 32
    assert BucketPolicy.from_spec("64,128").buckets == [64, 128]


def test_bucketed_generate_matches_unbucketed(_clean_cache_state):
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    from paddle_tpu.text import generation
    pt.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    m = GPTForCausalLM(cfg)
    ids = pt.randint(0, 64, [2, 5])
    ref = generation.generate(m, ids, max_new_tokens=6)
    got = generation.generate(m, ids, max_new_tokens=6,
                              shape_buckets="on")
    np.testing.assert_array_equal(got.numpy(), ref.numpy())
    snap = {r["name"]: r for r in _clean_cache_state.snapshot()}
    assert snap["generation_bucketed_calls_total"]["value"] >= 1


def test_bucketed_generate_respects_eos(_clean_cache_state):
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    from paddle_tpu.text import generation
    pt.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False)
    m = GPTForCausalLM(cfg)
    ids = pt.randint(0, 64, [1, 4])
    ref = generation.generate(m, ids, max_new_tokens=8, eos_token_id=3)
    got = generation.generate(m, ids, max_new_tokens=8, eos_token_id=3,
                              shape_buckets="on")
    np.testing.assert_array_equal(got.numpy(), ref.numpy())


# ===================================================================
# AOT deployment artifacts (non-donating inference program: safe to
# round-trip in-process — see the _MEMO comment for why donated
# executables are not)
# ===================================================================
class _TinyNet(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = pt.nn.Linear(8, 4)

    def forward(self, x):
        return pt.nn.functional.relu(self.fc(x))


def _export_aot(tmp_path):
    from paddle_tpu.jit.save_load import InputSpec, save_inference
    pt.seed(0)
    m = _TinyNet()
    m.eval()
    x = pt.to_tensor(np.random.RandomState(0).randn(2, 8)
                     .astype("float32"))
    path = os.path.join(str(tmp_path), "deploy")
    save_inference(m, path, [InputSpec([2, 8], "float32", "x")], aot=True)
    return path, x, m(x).numpy()


def test_aot_roundtrip_serves_without_compilation(tmp_path):
    from paddle_tpu.jit.save_load import load_inference
    path, x, ref = _export_aot(tmp_path)
    assert os.path.exists(os.path.join(path, "model.aotexec"))
    tl = load_inference(path)
    assert tl.is_aot
    np.testing.assert_allclose(tl(x).numpy(), ref, atol=1e-6)


def test_aot_refused_with_reason_on_stamp_mismatch(tmp_path,
                                                   _clean_cache_state):
    import json as _json
    from paddle_tpu.jit.save_load import (AOTIncompatible, load_inference)
    path, x, ref = _export_aot(tmp_path)
    meta_path = os.path.join(path, "inference_meta.json")
    with open(meta_path) as f:
        meta = _json.load(f)
    meta["aot"]["jax"] = "0.0.0-elsewhere"
    with open(meta_path, "w") as f:
        _json.dump(meta, f)
    # refuse-with-reason: the warning names exactly what diverged,
    # the portable StableHLO program still serves
    with pytest.warns(UserWarning, match="jax version mismatch"):
        tl = load_inference(path)
    assert not tl.is_aot
    np.testing.assert_allclose(tl(x).numpy(), ref, atol=1e-6)
    snap = {r["name"]: r for r in _clean_cache_state.snapshot()}
    assert snap["aot_artifact_refused_total"]["value"] >= 1
    # strict deployments turn the silent-recompile fallback into an error
    with pytest.raises(AOTIncompatible, match="jax version mismatch"):
        load_inference(path, strict_aot=True)


def test_aot_damaged_artifact_falls_back(tmp_path):
    from paddle_tpu.jit.save_load import load_inference
    path, x, ref = _export_aot(tmp_path)
    with open(os.path.join(path, "model.aotexec"), "r+b") as f:
        f.seek(8)
        f.write(b"\xa5" * 16)
    with pytest.warns(UserWarning, match="checksum mismatch"):
        tl = load_inference(path)
    assert not tl.is_aot
    np.testing.assert_allclose(tl(x).numpy(), ref, atol=1e-6)


def test_config_fingerprint_keys_hyperparams_not_runtime_state():
    """Instance constants the trace bakes in (momentum) must split the
    key; mutable runtime counters a checkpoint restore advances
    (optimizer step count) must NOT — else every warm restart misses."""
    from paddle_tpu import nn, optimizer as opt
    m1, m2 = nn.Linear(2, 1), nn.Linear(2, 1)
    o1 = opt.Momentum(learning_rate=0.05, momentum=0.9,
                      parameters=m1.parameters())
    o2 = opt.Momentum(learning_rate=0.05, momentum=0.5,
                      parameters=m2.parameters())
    assert cc.config_fingerprint(o1) != cc.config_fingerprint(o2)
    before = cc.config_fingerprint(o1)
    o1._step_count = 7              # what a restore mutates
    assert cc.config_fingerprint(o1) == before
    # and a FunctionCache keyed on it splits the digest
    import jax.numpy as jnp
    fc = FunctionCache("t", fingerprint=("src",))
    a = (jnp.ones((2, 3)),)
    assert (fc.digest(a, static=(cc.config_fingerprint(o1),))
            != fc.digest(a, static=(cc.config_fingerprint(o2),)))


# ===================================================================
# satellites riding along
# ===================================================================
def test_normalize_cost_analysis_shapes():
    # the installed jax returns the dict itself; a backend without a
    # cost model returns None
    assert normalize_cost_analysis(None) == {}
    assert normalize_cost_analysis({}) == {}
    assert normalize_cost_analysis({"flops": 2.0}) == {"flops": 2.0}
    cost = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), "float32")).compile().cost_analysis()
    assert normalize_cost_analysis(cost)["flops"] > 0
