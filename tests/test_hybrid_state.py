"""A hybrid decoder (text/solar_open2.py: gated NoPE GQA layers beside
gated delta-rule layers, one chip's share of the routed experts) against
the plain reference benchmark/references/solar_open2.py, at a small size
on the CPU, on seeded random weights: the model, the two forms of the
delta rule, the pool that holds K/V blocks and per-request state side by
side, and the engine through both.  Logits, never sampled tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.incubate.nn import DroplessMoE
from paddle_tpu.ops.nn_kernels import kda_chunk_k, kda_step_k
from paddle_tpu.ops.pallas import kda as kda_kernel
from paddle_tpu.serving.block_pool import BlockPool
from paddle_tpu.tensor import Tensor
from paddle_tpu.text.solar_open2 import (SolarOpen2Config,
                                         SolarOpen2ForCausalLM)
from benchmark.references import solar_open2 as ref

# one period: GQA, KDA, KDA, KDA; 4 heads of 32 over 2 kv heads; 4 KDA
# heads of 32; 16 routed experts of which this share holds 4, top 2 ...
SMALL = dict(
    vocab_size=96, hidden_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 32,
                        "num_heads": 4, "num_kv_heads": None},
    gqa_layers=[0, 4, 8], moe_intermediate_size=64, n_routed_experts=4,
    router_experts=16, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=1, norm_topk_prob=True, rms_norm_eps=1e-5,
    kda_allow_neg_eigval=True, kda_gate_rank=16, initializer_range=0.02,
    router_bias_seed=11)
# ... and one whose states the pallas step takes (8 KDA heads of 128)
LANED = dict(SMALL, linear_attn_config={
    "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 8,
    "num_kv_heads": None})
POSITIONS = 128


def build(cfg, dtype="float32", seed=5):
    lin = cfg["linear_attn_config"]
    with pt.LazyGuard():
        model = SolarOpen2ForCausalLM(SolarOpen2Config(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], gqa_layers=cfg["gqa_layers"],
            kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            kda_gate_rank=cfg["kda_gate_rank"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=cfg["router_experts"],
            held_experts=(0, cfg["n_routed_experts"]),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            max_position_embeddings=POSITIONS, dtype=dtype))
    weights = ref.init_weights(cfg, POSITIONS, seed, dtype=jnp.dtype(dtype))
    assert model.set_state_dict(ref.to_program(weights, cfg)) == ([], [])
    return model.eval(), weights


@pytest.fixture(scope="module")
def small():
    return build(SMALL)


def reference_logits(weights, cfg, ids):
    return np.asarray(ref.logits_fn(weights, jnp.asarray(ids)[None],
                                    cfg["num_attention_heads"])[0])


# ------------------------------------------------------------------ model
def test_model_forward_equals_the_reference(small):
    model, weights = small
    ids = np.random.default_rng(0).integers(0, 96, (2, 40))
    with pt.no_grad():
        got = np.asarray(model(pt.to_tensor(ids))._array)
    for row, have in zip(ids, got):
        np.testing.assert_allclose(
            have, reference_logits(weights, SMALL, row), atol=2e-5)


def test_layer_kinds_follow_gqa_layers_and_are_born_bfloat16():
    model, _ = build(SMALL, dtype="bfloat16")
    assert [b.kind for b in model.model.layers] == ["gqa", "kda", "kda",
                                                    "kda"]
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    planes = model.cache_planes()
    assert sorted(planes[0]) == ["k", "v"]
    assert sorted(planes[1]) == sorted(planes[3]) == ["state", "tail"]


@pytest.mark.parametrize("use_jit", [False, True], ids=["eager", "jit"])
def test_generate_follows_the_reference_argmax(small, use_jit):
    model, weights = small
    prompt = np.random.default_rng(1).integers(0, 96, (1, 9))
    out = np.asarray(model.generate(pt.to_tensor(prompt), max_new_tokens=5,
                                    use_jit=use_jit)._array)[0]
    want = reference_logits(weights, SMALL, out[:-1]).argmax(-1)[8:]
    np.testing.assert_array_equal(out[9:], want)


# ------------------------------------------------- the delta rule's forms
def _delta_inputs(b, t, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    # decays down to exp(-12) a position: exp(-500) over a chunk
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, d), minval=-4.0,
                                    maxval=2.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d))
    return q, k, v, g, beta, s0


def _recurrence(q, k, v, g, beta, s0):
    """The plain recurrence, one position after the other."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcd->bhd", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcd->bhd", qt, s)
    with jax.default_matmul_precision("highest"):
        s, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                            for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("cuts, bucket", [
    ((150,), 150), ((64, 64, 22), 64), ((1, 31, 100, 18), 128),
    ((37, 113), 256)], ids=["whole", "chunks", "ragged", "padded"])
def test_kda_chunk_over_any_split_equals_the_recurrence(cuts, bucket):
    """A sequence cut into engine chunks, each padded to a bucket with
    its `n_valid` real positions, carries the state from chunk to chunk
    to where the plain recurrence arrives."""
    q, k, v, g, beta, s0 = _delta_inputs(2, 150, 3, 16)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    state, outs, at = s0, [], 0
    for n in cuts:
        pad = lambda a: jnp.pad(a[:, at:at + n], [(0, 0), (0, bucket - n)]
                                + [(0, 0)] * (a.ndim - 2),
                                constant_values=0.37)
        o, state = kda_chunk_k(pad(q), pad(k), pad(v), -jnp.abs(pad(g)),
                               pad(beta), state,
                               n_valid=jnp.full((2,), n, jnp.int32))
        outs.append(o[:, :n])
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


def test_kda_step_token_by_token_equals_the_recurrence():
    q, k, v, g, beta, s0 = _delta_inputs(2, 12, 3, 16, seed=1)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    # rows 0 and 1 own slots 3 and 1 of a pool of 5; a dead third row
    pool = jnp.full((5, 3, 16, 16), 7.0).at[jnp.array([3, 1])].set(s0)
    slots, live = jnp.array([3, 1, 0]), jnp.array([True, True, False])
    row3 = lambda a: jnp.concatenate([a, a[:1]], 0)
    outs = []
    for t in range(12):
        o, pool = kda_step_k(*(row3(a[:, t]) for a in (q, k, v, g, beta)),
                             pool, slots, live)
        outs.append(o[:2])
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(pool[jnp.array([3, 1])], want_s, atol=2e-5)
    for untouched in (0, 2, 4):     # the dead row's slot among them
        assert bool((pool[untouched] == 7.0).all())


def test_the_interpreted_pallas_step_equals_the_xla_step():
    q, k, v, g, beta, _ = _delta_inputs(4, 1, 8, 128, seed=2)
    args = [a[:, 0] for a in (q, k, v, g, beta)]
    pool = jax.random.normal(jax.random.PRNGKey(3), (6, 8, 128, 128))
    slots, live = jnp.array([4, 0, 2, 5]), jnp.array([1, 0, 1, 0], bool)
    assert kda_kernel.supports(args[0].shape, pool.shape)
    want_o, want_pool = kda_step_k(*args, pool, slots, live)
    got_o, got_pool = kda_kernel.kda_decode_step(*args, pool, slots, live,
                                                 interpret=True)
    np.testing.assert_allclose(got_o[live], want_o[live], atol=1e-6)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-6)
    for untouched in (0, 1, 3, 5):  # two dead rows' slots among them
        np.testing.assert_array_equal(got_pool[untouched], pool[untouched])


# ------------------------------------------------------------ both pools
def test_the_pool_holds_blocks_and_slots_side_by_side(small):
    model, _ = small
    pool = BlockPool.for_model(model, num_blocks=10, block_size=8, slots=3)
    assert [a is not None for a in pool.planes["k"]] == [True, False,
                                                         False, False]
    assert [a is not None for a in pool.planes["state"]] == [False, True,
                                                             True, True]
    assert pool.plane_shapes() == {"k": (10, 8, 2, 32), "v": (10, 8, 2, 32)}
    assert pool.planes["state"][1].shape == (3, 4, 32, 32)
    assert pool.planes["state"][1].dtype == jnp.float32
    assert pool.planes["tail"][1].shape == (3, 3, 3 * 4 * 32)
    assert pool.state_bytes() == 3 * (4 * 32 * 32 * 4 + 3 * 384 * 4)
    got = [pool.allocate_slot() for _ in range(4)]
    assert sorted(got[:3]) == [0, 1, 2] and got[3] is None
    assert pool.check_leaks()[0] == [("slot", 0), ("slot", 1), ("slot", 2)]
    for n in got[:3]:
        pool.free_slot(n)
    with pytest.raises(ValueError):
        pool.free_slot(1)
    assert pool.check_leaks() == ([], [])


@pytest.mark.parametrize("family", ["gpt", "deepseek"])
def test_one_kind_models_allocate_the_bytes_they_did(family):
    """A K/V-only and a latent-only model: every layer the same planes,
    no slots, the bytes of [layers x blocks x block x trailing]."""
    if family == "gpt":
        from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=64, num_layers=3, num_heads=4,
            max_position_embeddings=64))
        per_token = 2 * 4 * 16
    else:
        from paddle_tpu.text.deepseek import (DeepseekV3Config,
                                              DeepseekV3ForCausalLM)
        model = DeepseekV3ForCausalLM(DeepseekV3Config(
            vocab_size=64, hidden_size=64, num_layers=3, num_heads=4,
            intermediate_size=128, kv_lora_rank=64, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, moe_intermediate_size=32,
            n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2))
        per_token = 128
    eng = serving.LLMEngine(model, num_blocks=12, block_size=8,
                            max_running=4)
    pool = eng.pool
    assert pool.slots == 0 and not pool.state_names
    arrays = [a for plane in pool.planes.values() for a in plane]
    assert all(a is not None for a in arrays)
    assert sum(a.nbytes for a in arrays) == 3 * 12 * 8 * per_token * 4
    # and the programs take what they took: no slots ride along
    _, structs = eng.program_structs(("decode",))
    assert len(structs) == 9
    assert len(eng.program_structs(("prefill", 32))[1]) == 7
    assert eng.close() == ([], [])


def _serve(model, prompts, new_tokens, **engine):
    eng = serving.LLMEngine(model, **engine)
    served, emit = {}, eng._emit

    def keep(req, row, now):
        served.setdefault(req.id, []).append(np.array(row))
        return emit(req, row, now)

    eng._emit = keep
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new_tokens)]
    eng.run()
    return eng, reqs, served


@pytest.mark.parametrize("cfg, pallas", [(SMALL, None),
                                         (LANED, "interpret")],
                         ids=["xla", "kernel"])
def test_served_logits_equal_the_references_full_forward(cfg, pallas,
                                                         monkeypatch):
    """Prefill in chunks (the chunkwise form, state and tail carried from
    chunk to chunk, buckets padded) + decode through the K/V pool AND the
    state pool (one step a row, in place; the XLA scatter or the pallas
    step interpreted) = the reference's full forward at every served
    position."""
    if pallas:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    model, weights = build(cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n) for n in (21, 1, 13, 40, 9, 70)]
    eng, reqs, served = _serve(model, prompts, (6, 9, 4, 7, 3, 5),
                               num_blocks=40, block_size=8, max_running=4,
                               prefill_chunk=16)
    for req, prompt in zip(reqs, prompts):
        fed = np.concatenate([prompt, req.generated[:-1]])
        want = reference_logits(weights, cfg, fed)[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(served[req.id]), want,
                                   atol=5e-5)
    from paddle_tpu.observability import metrics, trace
    roots = [s[6] for s in trace.spans() if s[0] == "serving.step"
             and s[6].get("decode_rows")][-6:]
    per_row = eng.pool.state_bytes()
    for c in roots:
        assert c["state_slots_live"] == c["decode_rows"]
        assert c["state_bytes_rw"] == 2 * per_row * c["decode_rows"]
        if "moe_assignments_routed" in c and c["moe_assignments_routed"]:
            # the routers' 2 a row a layer against what fell on the 4 held
            assert c["moe_assignments"] <= c["moe_assignments_routed"]
            assert c["experts_touched"] <= min(16, c["moe_assignments"])
    assert any(c.get("moe_assignments_routed") for c in roots)
    assert metrics.registry().gauge("serving_state_slots_in_use").value == 0
    assert eng.pool.free_slots == 4
    assert eng.close() == ([], [])


def test_a_decode_program_leaves_other_slots_states_bit_identical(small):
    """The decode program runs over all `max_running` rows: a dead row
    and a request in the middle of its prefill keep their state to the
    bit; only the decoding row's slot moves."""
    model, _ = small
    eng = serving.LLMEngine(model, num_blocks=40, block_size=8,
                            max_running=4, prefill_chunk=16)
    rng = np.random.default_rng(2)
    short = eng.add_request(rng.integers(0, 96, 5), max_new_tokens=8)
    long = eng.add_request(rng.integers(0, 96, 90), max_new_tokens=2)
    while not (short.decode_ready and long.needs_prefill and long.ctx):
        eng.step()
    eng._land(eng._flight, None, __import__("collections").Counter())
    before = {n: [None if a is None else np.asarray(a) for a in plane]
              for n, plane in eng.pool.planes.items()}
    flight = eng._dispatch([short])
    flight.ids.block_until_ready()
    after = eng.pool.planes
    moved = short.state_slot
    for name in ("state", "tail"):
        for was, now in zip(before[name][1:], after[name][1:]):
            now = np.asarray(now)
            for slot in range(4):
                same = (was[slot] == now[slot]).all()
                assert same == (slot != moved), (name, slot)
    assert long.state_slot != moved
    eng._flight = flight
    eng.run()
    assert eng.close() == ([], [])


def test_preempt_and_readmit_reproduces_the_logits(small):
    """A pool too small for the running rows evicts the youngest; it
    comes back from the zero state (recompute) and its logits are the
    reference's all the same."""
    model, weights = small
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n) for n in (30, 28, 26)]
    eng, reqs, served = _serve(model, prompts, (24, 24, 24), num_blocks=17,
                               block_size=8, max_running=3,
                               prefill_chunk=32)
    assert sum(r.preemptions for r in reqs) >= 1
    for req, prompt in zip(reqs, prompts):
        fed = np.concatenate([prompt, req.generated[:-1]])
        want = reference_logits(weights, SMALL, fed)[len(prompt) - 1:]
        got = np.stack(served[req.id])
        # a preempted row's position may have been served twice: the
        # last pass at every position is the one that was emitted
        np.testing.assert_allclose(got[-len(want):], want[-len(got):],
                                   atol=5e-5)
        assert len(req.generated) == 24
    assert eng.pool.free_slots == 3
    assert eng.close() == ([], [])


def test_slots_and_blocks_all_home_after_an_overload_drill(small):
    model, _ = small
    eng = serving.LLMEngine(model, num_blocks=24, block_size=8,
                            max_running=3, prefill_chunk=16)
    rng = np.random.default_rng(4)
    reqs = [eng.add_request(rng.integers(0, 96, int(n)),
                            max_new_tokens=int(k))
            for n, k in zip(rng.integers(1, 60, 14),
                            rng.integers(1, 20, 14))]
    for i in range(200):
        if not eng.has_work:
            break
        eng.step()
        if i in (3, 9, 15):     # a running one and a waiting one go
            for req in (eng.scheduler.running[-1:]
                        + list(eng.scheduler.waiting)[-1:]):
                eng.cancel(req)
    assert not eng.has_work
    assert all(r.finish_reason in ("length", "cancelled") for r in reqs)
    assert all(r.state_slot is None and not r.block_table for r in reqs)
    assert eng.pool.check_leaks() == ([], [])
    assert (eng.pool.free_slots, eng.pool.free_blocks) == (3, 24)
    assert eng.close() == ([], [])


# -------------------------------------------------------------- the share
def test_all_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test: the routed parts that all 4 shares of 4
    experts give, plus the shared expert counted once, are the uncut
    reference's expert layer; and a held layer's load sums to the
    assignments that fell on its experts."""
    uncut = dict(SMALL, n_routed_experts=16)
    weights = ref.init_weights(uncut, POSITIONS, 9, dtype=jnp.float32)
    lp = weights["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(0), (24, 128))
    want, _ = ref._experts(m, lp, weights.hyper, "float32")
    shared = ref._swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"],
                         "float32")
    names = {"gate_weight": "router", "score_bias": "bias",
             "shared_gate": "s_gate", "shared_up": "s_up",
             "shared_down": "s_down"}
    routed, loads = 0.0, []
    for first in range(0, 16, 4):
        layer = DroplessMoE(128, 64, 16, 2, scoring="sigmoid",
                            score_bias=True, num_shared=1,
                            held=(first, 4)).eval()
        state = {k: lp[v] for k, v in names.items()}
        state.update({f"w_{k}": lp[f"e_{k}"][first:first + 4]
                      for k in ("gate", "up", "down")})
        assert layer.set_state_dict(state) == ([], [])
        y, load = layer(Tensor._from_array(m),
                        live=Tensor._from_array(jnp.ones(24, bool)))
        routed = routed + (y._array - shared)
        loads.append(np.asarray(load._array))
        assert load.shape == [4]
    np.testing.assert_allclose(routed + shared, want, atol=2e-6)
    picked = np.asarray(jax.lax.top_k(
        jax.nn.sigmoid(m @ lp["router"]) + lp["bias"], 2)[1])
    for i, load in enumerate(loads):
        local = (picked // 4 == i)
        assert load.sum() == local.sum()
        np.testing.assert_array_equal(
            load, [(picked == 4 * i + e).sum() for e in range(4)])
    assert sum(int(load.sum()) for load in loads) == 24 * 2
