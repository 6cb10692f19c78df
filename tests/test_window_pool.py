"""The pool of two lifetimes (`serving/block_pool.py` block GROUPS, the
scheduler's `reserve` / `trim`, the engine's table a group) and the band
in the paged ops, small sizes on the CPU."""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import metrics, trace as span_trace
from paddle_tpu.ops import pallas as plo
from paddle_tpu.ops.nn_kernels import paged_attention_k
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.block_pool import BlockPool, PoolExhausted
from paddle_tpu.serving.scheduler import Request, Scheduler
from paddle_tpu.text.decode import LayerPlanes
from paddle_tpu.text.generation import generate
from paddle_tpu.text.laguna import LagunaConfig, LagunaForCausalLM

WINDOW, BS, CHUNK = 8, 4, 16


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LagunaConfig(
        vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
        intermediate_size=128, max_position_embeddings=256, num_kv_heads=2,
        head_dim=16, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        sliding_window=WINDOW, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        held_experts=(0, 4), initializer_range=0.2)
    m = LagunaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    kw = dict(dict(num_blocks=64, block_size=BS, max_running=4,
                   prefill_chunk=CHUNK), **kw)
    return LLMEngine(model, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def _alone(model, prompts, new):
    """Each prompt's tokens served alone from a roomy pool (that the
    engine agrees with `generate` is the ten-windows test's to show)."""
    eng = _engine(model, max_running=1)
    return [eng.generate_batch([p], max_new_tokens=new)[0] for p in prompts]


def _window_llama():
    """A model whose every layer is of the window kind (window 6)."""
    from paddle_tpu.text.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, max_position_embeddings=128,
        sliding_window=6, tensor_parallel=False))
    m.eval()
    return m


def _sequential(model, prompt, new):
    out = generate(model, pt.to_tensor(np.asarray([prompt], "int64")),
                   max_new_tokens=new).numpy()
    return out[0, len(prompt):].tolist()


# ------------------------------------------------------------------ the pool
def test_a_group_per_lifetime_each_with_its_own_blocks(model):
    eng = _engine(model)
    full, window = eng.pool.groups
    assert (full.name, full.window, full.layers) == ("full", None, [0, 4])
    assert (window.name, window.window, window.layers) \
        == ("window", WINDOW, [1, 2, 3])
    bound = -(-(WINDOW - 1 + CHUNK) // BS) + 1
    assert eng.pool.band_blocks(1, CHUNK) == bound == 7
    assert eng.pool.band_blocks(0, CHUNK) is None
    assert (full.num_blocks, window.num_blocks) == (64, 4 * bound)
    shapes = {i: eng.pool.planes["k"][i].shape[0] for i in range(5)}
    assert shapes == {0: 64, 1: 28, 2: 28, 3: 28, 4: 64}
    assert eng.pool.plane_shapes(1)["k"] == (28, BS, 2, 16)
    # the first group answers to the names the pool had before the groups
    assert eng.pool.num_blocks == eng.pool.free_blocks == 64
    # the engine works the window group's size out; a pool built by hand
    # takes it as given
    assert [g.num_blocks for g in BlockPool.for_model(
        model, 9, block_size=BS, window_blocks={WINDOW: 11}).groups] \
        == [9, 11]


def test_a_model_of_one_kind_is_one_group():
    pool = BlockPool(3, 10, 4, {"k": (2, 8), "v": (2, 8)})
    assert len(pool.groups) == 1 and pool.groups[0].window is None
    assert pool.group_of == [0, 0, 0] and pool.num_blocks == 10
    ids = pool.allocate(3)
    assert pool.used_blocks == 3 and pool._refs.count(1) == 3
    pool.free(ids)
    assert sorted(pool._free) == list(range(10))
    # one window for every layer: still one group, and it has the window
    planes = [LayerPlanes({"k": (2, 8)}, window=6)] * 2
    pool = BlockPool(2, 5, 4, planes)
    assert [(g.name, g.window, g.num_blocks) for g in pool.groups] \
        == [("window", 6, 5)]


def test_a_window_group_trimmed_blocks_come_back_lowest_first():
    """`Scheduler.trim` hands a window group's blocks behind the band
    back to that group alone, and its next allocation takes them
    first."""
    planes = [LayerPlanes({"k": (2, 8)}),
              LayerPlanes({"k": (2, 8)}, window=WINDOW)]
    pool = BlockPool(2, 20, BS, planes, window_blocks={WINDOW: 8})
    sched = Scheduler(pool, max_running=1, run_tokens=CHUNK)
    req = Request(list(range(40)), max_new_tokens=4)
    sched.submit(req)
    assert sched.admit() == [req]
    assert sched.reserve(req, 16)
    assert req.block_tables[1] == [0, 1, 2, 3]
    req.ctx = 16
    assert sched.trim(req) == (16 - WINDOW) // BS == 2
    assert pool.used_in(1) == 2 and pool.free_blocks == 20 - 11
    assert sched.reserve(req, 24)
    assert req.block_tables[1][2:] == [2, 3, 0, 1]
    sched.trim(req)
    sched.finish(req, "length")
    assert pool.check_leaks() == ([], [])


def test_ten_windows_hold_a_band_of_window_blocks_and_a_full_table(model):
    eng = _engine(model)
    bound = eng.pool.band_blocks(1, CHUNK)
    prompt, new = _prompts([10 * WINDOW])[0], 12
    req = eng.add_request(prompt, max_new_tokens=new)
    seen, freed = [], 0
    while eng.has_work:
        span_trace.clear()
        eng.step()
        root = [r for r in span_trace.spans() if r[0] == "serving.step"][-1]
        freed += root[6].get("window_blocks_freed", 0)
        if req.finish_reason is None and req.block_tables[1]:
            held = len(req.block_tables[1]) - req.behind[1]
            seen.append(held)
            # the full kind's table grows with the context
            assert len(req.block_tables[0]) >= eng.pool.blocks_for(req.ctx)
            if req.in_flight:       # a decode row: what the root counted
                whole = eng.pool.blocks_for(req.ctx)
                assert root[6]["window_blocks_live"] <= bound
                assert root[6]["window_blocks_live"] \
                    + root[6]["window_blocks_saved"] == whole
                assert root[6]["window_blocks_band"] in (2, 3)
    assert max(seen) <= bound < eng.pool.blocks_for(len(prompt))
    # between programs a request holds its band alone
    assert max(seen[-new:]) <= -(-(WINDOW + 1) // BS) + 1
    assert freed >= eng.pool.blocks_for(len(prompt)) - bound
    assert req.generated == _sequential(model, prompt, new)
    assert eng.pool.check_leaks() == ([], [])


def test_freed_ids_serve_another_request_while_the_first_decodes(model):
    """The free list hands out its lowest ids first: the second
    request's chunks run on the blocks the first has handed back, while
    the first still decodes; neither's tokens change."""
    eng = _engine(model, max_running=2)
    first, second = _prompts([50, 45], seed=3)
    a = eng.add_request(first, max_new_tokens=30)
    live = lambda r: set(r.block_tables[-1][r.behind[-1]:])
    b, had, reused = None, set(), set()
    while eng.has_work:
        eng.step()
        if b is None and a.generated:
            b = eng.add_request(second, max_new_tokens=10)
        if a.finish_reason is None:
            had |= live(a)
            if b is not None:
                assert not live(a) & live(b)
                reused |= live(b) & had
    assert reused       # ids the first request held and handed back
    assert [a.generated] == _alone(model, [first], 30)
    assert [b.generated] == _alone(model, [second], 10)
    assert eng.pool.check_leaks() == ([], [])


def test_preemption_and_resume_after_blocks_went_home(model):
    """A full group so small that the longer request evicts the other:
    the victim's window blocks had gone home behind its band; it
    re-prefills from nothing and gives the same tokens."""
    prompts = _prompts([40, 38], seed=5)
    reg = metrics.registry()
    base = reg.counter("serving_requests_preempted_total").value
    eng = _engine(model, num_blocks=24, max_running=2)
    reqs = [eng.add_request(p, max_new_tokens=16) for p in prompts]
    eng.run(max_steps=2000)
    assert reg.counter("serving_requests_preempted_total").value > base
    assert sum(r.preemptions for r in reqs) >= 1
    for req, want in zip(reqs, _alone(model, prompts, 16)):
        assert req.finish_reason == "length"
        assert req.generated == want
    assert eng.pool.check_leaks() == ([], [])
    assert eng.pool.free_blocks == 24
    assert eng.pool.used_in(1) == 0


def test_overload_with_injected_exhaustion_leaks_no_block_of_either_kind(
        model):
    prompts = _prompts([30, 21, 44, 9, 37, 26], seed=7)
    reg = metrics.registry()
    total = reg.counter("serving_pool_exhausted_total").value
    kinds = {k: reg.counter("serving_pool_exhausted_total", kind=k).value
             for k in ("full", "window")}
    with chaos.scoped("serving.pool_exhausted@5*4"):
        eng = _engine(model, num_blocks=30, max_running=4)
        reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        eng.run(max_steps=5000)
    assert all(r.finish_reason == "length" for r in reqs)
    for req, want in zip(reqs, _alone(model, prompts, 8)):
        assert req.generated == want
    assert eng.pool.check_leaks() == ([], [])
    assert eng.close() == ([], [])
    fired = reg.counter("serving_pool_exhausted_total").value - total
    by_kind = sum(reg.counter("serving_pool_exhausted_total", kind=k).value
                  - v for k, v in kinds.items())
    assert fired >= 4 and by_kind == fired


def test_a_request_no_group_could_hold_is_refused_by_kind(model):
    eng = _engine(model, num_blocks=8)
    with pytest.raises(PoolExhausted, match="full blocks"):
        eng.add_request(_prompts([40])[0], max_new_tokens=8)
    # a window group smaller than one request's band: only a model of
    # window layers alone can have one (its group is the user's)
    eng = LLMEngine(_window_llama(), num_blocks=3, block_size=4,
                    prefill_chunk=8)
    with pytest.raises(PoolExhausted, match="window blocks"):
        eng.add_request(_prompts([40])[0], max_new_tokens=8)
    leaked = _engine(model)
    leaked.pool.allocate(2, 1)
    assert leaked.pool.check_leaks() == ([("window", 0), ("window", 1)], [])


def test_a_window_no_plane_declares_is_still_refused(model):
    class Undeclared(LagunaForCausalLM):
        def cache_planes(self):
            return [dict(p) for p in super().cache_planes()]

    other = Undeclared(model.cfg)
    with pytest.raises(NotImplementedError, match="cache_planes"):
        LLMEngine(other, num_blocks=16, block_size=BS)


def test_llama_with_a_sliding_window_serves_from_the_window_kind():
    m = _window_llama()
    assert {p.window for p in m.cache_planes()} == {6}
    eng = LLMEngine(m, num_blocks=12, block_size=4, max_running=2,
                    prefill_chunk=8)
    assert [(g.name, g.window) for g in eng.pool.groups] == [("window", 6)]
    prompts = [np.random.default_rng(1).integers(0, 128, n).tolist()
               for n in (30, 17)]
    # two requests of 30 + 8 and 17 + 8 positions in 12 blocks of 4: only
    # because the blocks behind the band go home
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    eng.run(max_steps=500)
    for req, prompt in zip(reqs, prompts):
        assert req.preemptions == 0
        assert req.generated == _sequential(m, prompt, 8)
    assert eng.pool.check_leaks() == ([], [])


@pytest.mark.parametrize("num_blocks, at_once", [(7, 1), (10, 2), (16, 3)])
def test_admission_counts_the_band_so_no_request_evicts_another(
        num_blocks, at_once):
    """Window layers alone and a pool of a band or two (a request holds
    up to cdiv(6 + 8 - 1, 4) + 1 = 5 blocks): admission lets in what the
    group holds bands for, nobody is preempted, nobody prefills twice."""
    m = _window_llama()
    eng = LLMEngine(m, num_blocks=num_blocks, block_size=4, max_running=3,
                    prefill_chunk=8)
    assert eng.scheduler.most_blocks(0, 38) == 5
    assert eng.scheduler.most_blocks(0, 9) == 3     # a short request's all
    prompts = [np.random.default_rng(2).integers(0, 128, n).tolist()
               for n in (30, 17, 25)]
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    most, prefilled = 0, 0
    while eng.has_work:
        prefilled += eng.step()["prefilled"]
        most = max(most, len(eng.scheduler.running))
        assert eng.pool.used_in(0) <= 5 * len(eng.scheduler.running)
    assert most == at_once
    assert prefilled == sum(len(p) - 1 for p in prompts)
    for req, prompt in zip(reqs, prompts):
        assert req.preemptions == 0 and req.finish_reason == "length"
        assert req.generated == _sequential(m, prompt, 8)
    assert eng.pool.check_leaks() == ([], [])


def test_admission_counts_both_kinds_in_a_pool_built_by_hand(model):
    """The scheduler against a pool whose window group holds two bands
    and a block: the third request waits for a band, not for a full
    block, and enters when one comes home."""
    bound = -(-(WINDOW - 1 + CHUNK) // BS) + 1
    pool = BlockPool.for_model(model, 64, block_size=BS, slots=4,
                               window_blocks={WINDOW: 2 * bound + 1})
    sched = Scheduler(pool, max_running=4, run_tokens=CHUNK)
    reqs = [Request(p, max_new_tokens=8) for p in _prompts([40, 40, 40])]
    for req in reqs:
        sched.submit(req)
    assert sched.admit() == reqs[:2]
    assert reqs[2].admit_skips == 1 and pool.free_blocks == 64 - 2 * 11
    # the full kind's blocks were handed out, the window kind's counted
    assert pool.used_in(1) == 0 and reqs[2].block_tables == [[], []]
    sched.finish(reqs[0], "length")
    assert sched.admit() == reqs[2:]
    for req in reqs[1:]:
        sched.finish(req, "length")
    assert pool.check_leaks() == ([], [])


# ------------------------------------------------------------- the band ops
def _dense(q, k, v, n, window, scale):
    """Masked softmax of one query [H, D] at position n - 1 over its own
    row's keys [L, Hkv, D]."""
    g = q.shape[0] // k.shape[1]
    lo = max(0, n - window) if window else 0
    kk, vv = (np.repeat(a[lo:n], g, axis=1) for a in (k, v))
    s = np.einsum("hd,lhd->hl", q, kk) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hl,lhd->hd", p / p.sum(-1, keepdims=True), vv)


# contexts below, at and far above the window, block-aligned and not
LENS = [1, 3, 8, 9, 12, 16, 17, 40, 41, 63]


@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("window", [8, 7, None])
def test_decode_band_xla_and_kernel_against_a_dense_masked_softmax(
        group, window):
    rng = np.random.default_rng(group)
    hkv, d, m = 2, 128, 16
    h, b = hkv * group, len(LENS)
    n_blocks = b * m + 1
    kp, vp = (rng.standard_normal((n_blocks, BS, hkv, d)).astype("float32")
              for _ in range(2))
    tables = (rng.permutation(n_blocks - 1)[:b * m].reshape(b, m)
              + 1).astype("int32")
    q = rng.standard_normal((b, 1, h, d)).astype("float32")
    want = np.stack([
        _dense(q[i, 0], kp[tables[i]].reshape(m * BS, hkv, d),
               vp[tables[i]].reshape(m * BS, hkv, d), n, window, d ** -0.5)
        for i, n in enumerate(LENS)])
    if window:      # what the pool does to the entries behind the band
        for i, n in enumerate(LENS):
            tables[i, :max(n - window, 0) // BS] = 0
        kp[0] = vp[0] = np.nan
    args = [jnp.asarray(a) for a in (q, kp, vp, tables)]
    pos = jnp.asarray(np.asarray(LENS) - 1, jnp.int32)
    xla = paged_attention_k(*args, pos, window=window)
    kernel = pa.paged_decode_attention(*args, pos + 1, interpret=True,
                                       window=window)
    assert np.abs(np.asarray(xla)[:, 0] - want).max() < 2e-5
    assert np.abs(np.asarray(kernel)[:, 0] - want).max() < 2e-5


@pytest.mark.parametrize("pos, s", [(0, 5), (3, 6), (21, 7), (40, 16)])
def test_a_chunk_gathers_only_the_columns_it_can_see(pos, s):
    rng = np.random.default_rng(s)
    hkv, h, d, m, window = 2, 12, 32, 16, 8
    kp, vp = (rng.standard_normal((40, BS, hkv, d)).astype("float32")
              for _ in range(2))
    table = (rng.permutation(39)[:m] + 1).astype("int32")[None]
    q = rng.standard_normal((1, s, h, d)).astype("float32")
    K, V = (a[table[0]].reshape(m * BS, hkv, d) for a in (kp, vp))
    want = np.stack([_dense(q[0, i], K, V, pos + i + 1, window, d ** -0.5)
                     for i in range(s)])
    table[0, :max(pos - window, 0) // BS] = 0
    kp[0] = vp[0] = np.nan
    got = paged_attention_k(*(jnp.asarray(a) for a in (q, kp, vp, table)),
                            jnp.asarray([pos], jnp.int32), window=window)
    assert np.abs(np.asarray(got)[0] - want).max() < 2e-5


def test_walked_blocks_is_what_the_kernel_walks():
    """The host's count against the kernel's own walk: the blocks whose
    contents can move the result."""
    window, m = 8, 16
    assert pa.walked_blocks(LENS, m, BS) == sum(-(-n // BS) for n in LENS)
    per_row = [-(-n // BS) - max(n - window, 0) // BS for n in LENS]
    assert pa.walked_blocks(LENS, m, BS, window) == sum(per_row)
    assert max(per_row) == pa.band_blocks(window, BS) == 3
    assert pa.chunk_blocks(m, BS, 2, 128, jnp.float32, window) <= 3
    # a dead slot walks one block, windowed or not
    assert pa.walked_blocks([0, 1], m, BS, window) == 2
    rng = np.random.default_rng(0)
    hkv, h, d = 2, 12, 128
    n_blocks = len(LENS) * m + 1
    kp, vp = (rng.standard_normal((n_blocks, BS, hkv, d)).astype("float32")
              for _ in range(2))
    tables = (np.arange(len(LENS) * m).reshape(len(LENS), m) + 1
              ).astype("int32")
    q = jnp.asarray(rng.standard_normal((len(LENS), 1, h, d)), jnp.float32)
    lens = jnp.asarray(LENS, jnp.int32)

    def run(v):
        return np.asarray(pa.paged_decode_attention(
            q, jnp.asarray(kp), jnp.asarray(v), jnp.asarray(tables), lens,
            interpret=True, window=window))

    base = run(vp)
    for i in (0, 3, 5, 7, 9):       # every column of five rows' tables
        n = LENS[i]
        first, last = max(n - window, 0) // BS, -(-n // BS)
        for col in range(m):
            poisoned = vp.copy()
            poisoned[tables[i, col]] += 100.0
            moved = not np.array_equal(run(poisoned)[i], base[i])
            assert moved == (first <= col < last), (n, col)
    # the op's own count follows the path that serves the call
    shape = (n_blocks, BS, hkv, d)
    assert plo.paged_blocks_read(LENS, m, (len(LENS), 1, h, d), shape,
                                 jnp.float32, window=window) \
        == len(LENS) * (-(-window // BS) + 1)      # the CPU gathers
