"""`text/laguna.py` against its plain reference
(`benchmark/references/laguna.py`) on seeded weights, small size, CPU:
one forward, the three cache forms (growing, preallocated, the serving
pool with blocks gone home before the compared positions), the controls
that must fail the same comparison, and the share of the experts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import harness
from benchmark.references import laguna as ref
from paddle_tpu.serving import LLMEngine
from paddle_tpu.text.laguna import (LagunaConfig, LagunaForCausalLM,
                                    rope_frequencies)

CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "laguna-s-2.1.json")))
# the file's own small size (hidden 128, heads 4 and 6 over 2, window 8,
# 4 of 16 experts held, top 2), in float32 and wider weights so that a
# wrong model shows
SMALL = dict(CONFIG, **CONFIG["rehearsal"], initializer_range=0.2)
SMALL["model_kwargs"] = dict(SMALL["model_kwargs"], dtype="float32",
                             initializer_range=0.2)
SEED, LENGTH = 2 ** 31 + 34, 96       # twelve windows
TOL = 2e-4                            # float32 on both sides


@pytest.fixture(scope="module")
def weights():
    with jax.default_matmul_precision("highest"):
        return ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    pt.seed(0)
    m = harness.build_model(SMALL)
    missing, unexpected = m.set_state_dict(ref.to_program(weights, SMALL))
    assert not missing and not unexpected
    m.eval()
    return m


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, 512, (2, LENGTH))


@pytest.fixture(scope="module")
def want(weights, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_fn(weights, jnp.asarray(ids)))


def _logits(model, ids, caches=None):
    return np.asarray(model(pt.to_tensor(np.asarray(ids, "int64")),
                            caches=caches)._array)


def test_the_layers_are_of_two_kinds(model):
    blocks = model.model.layers
    assert [b.self_attn.window for b in blocks] == [None, 8, 8, 8, None]
    assert [b.self_attn.heads for b in blocks] == [4, 6, 6, 6, 4]
    assert [b.routed for b in blocks] == [False, True, True, True, True]
    assert blocks[1].self_attn.g_proj.weight.shape == [128, 6]
    assert blocks[1].mlp.w_gate.shape == [4, 128, 64]
    assert blocks[1].mlp.gate_weight.shape == [128, 16]
    # the window kind turns the whole head, the full kind half of it
    assert [b.self_attn.rope[1] for b in blocks] == [16, 32, 32, 32, 16]
    assert blocks[0].self_attn.rope[2] == pytest.approx(1.4852030263919618)
    planes = model.cache_planes()
    assert [p.window for p in planes] == [None, 8, 8, 8, None]
    assert [p.kind for p in planes] == ["full", "window", "window",
                                        "window", "full"]


def test_yarn_frequencies_at_the_published_size():
    """Hand arithmetic over the published rope_parameters: r = 64,
    lo, hi = floor / ceil of 64 ln(8192 / (beta 2 pi)) / (2 ln 500000)."""
    inv, r, factor = rope_frequencies(
        CONFIG["rope_parameters"]["full_attention"], 128)
    assert (r, len(inv)) == (64, 32) and factor == 1.4852030263919618
    lo = int(np.floor(64 * np.log(8192 / (32 * 2 * np.pi))
                      / (2 * np.log(5e5))))
    hi = int(np.ceil(64 * np.log(8192 / (2 * np.pi)) / (2 * np.log(5e5))))
    assert (lo, hi) == (9, 18)
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:lo + 1], plain[:lo + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[hi:], plain[hi:] / 128, rtol=1e-6)
    mid = (lo + hi) // 2
    m = 1 - (mid - lo) / (hi - lo)
    assert inv[mid] == pytest.approx(
        (1 - m) * plain[mid] / 128 + m * plain[mid], rel=1e-6)
    # the reference states the same rule on its own
    np.testing.assert_allclose(ref.rope_frequencies(
        CONFIG["rope_parameters"]["full_attention"], 128)[0], inv,
        rtol=1e-6)
    inv, r, factor = rope_frequencies(
        CONFIG["rope_parameters"]["sliding_attention"], 128)
    assert (r, factor) == (128, 1.0)
    np.testing.assert_allclose(inv, 1e4 ** (-np.arange(64) / 64.0),
                               rtol=1e-6)


def test_one_forward_agrees_with_the_reference(model, ids, want):
    assert np.abs(_logits(model, ids) - want).max() < TOL
    assert np.abs(want).max() > 1.0


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_wrong_model_fails_the_same_comparison(model, weights, ids, fault):
    """Each control is the reference with one thing wrong: the window left
    off, off by one, the two kinds' RoPE swapped, the rotary part 1.0 for
    0.5, attention_factor 1, the gate left off, the scaling 1 for 2.5."""
    with jax.default_matmul_precision("highest"):
        wrong = np.asarray(ref.logits_fn(weights, jnp.asarray(ids),
                                         precision="float32+" + fault))
    assert np.abs(_logits(model, ids) - wrong).max() > 100 * TOL


def test_growing_cache_prefill_then_decode(model, ids, want):
    # every step has a shape of its own (eager): five windows of prompt,
    # then a few tokens
    caches = model.new_caches(2, dtype="float32")
    got = [_logits(model, ids[:, :40], caches)]
    got += [_logits(model, ids[:, t:t + 1], caches) for t in range(40, 46)]
    assert np.abs(np.concatenate(got, 1) - want[:, :46]).max() < TOL


def test_preallocated_cache_in_chunks_then_decode(model, ids, want):
    caches = model.new_caches(2, dtype="float32", max_length=LENGTH)
    got, t = [], 0
    for n in [32, 32, 24] + [1] * 8:
        for c in caches:
            c["pos"] = pt.to_tensor(np.asarray(t, "int32"))
        got.append(_logits(model, ids[:, t:t + n], caches))
        t += n
    assert np.abs(np.concatenate(got, 1) - want).max() < TOL


def test_generate_eager_and_jitted_agree(model, ids):
    from paddle_tpu.text.generation import generate
    prompt = pt.to_tensor(ids[:1, :20].astype("int64"))
    eager = generate(model, prompt, max_new_tokens=4).numpy()
    jitted = model.generate(prompt, max_new_tokens=4).numpy()
    assert eager.shape == (1, 24) and np.array_equal(eager, jitted)


def _serve_logits(model, prompts, new, **engine):
    """Served logits of every decoded position: {request index: [rows]}."""
    eng = LLMEngine(model, **engine)
    emit, rows = eng._emit, {}

    def keep(req, row, now):
        rows.setdefault(req.id, []).append(np.array(row))
        return emit(req, row, now)
    eng._emit = keep
    reqs = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    held = 0
    while eng.has_work:
        eng.step()
        held = max([held] + [len(r.block_tables[1]) - r.behind[1]
                             for r in eng.scheduler.running])
    assert eng.pool.check_leaks() == ([], [])
    return reqs, [np.stack(rows[r.id]) for r in reqs], held, eng


def test_chunked_prefill_then_decode_through_the_pool(model, weights):
    """Contexts of several windows: the window layers' early blocks have
    gone home before the compared positions; the logits of every decoded
    position agree with the reference's full forward over prompt and
    served tokens."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, n).tolist() for n in (70, 9, 41)]
    reqs, rows, held, eng = _serve_logits(
        model, prompts, 20, num_blocks=80, block_size=4, max_running=4,
        prefill_chunk=16)
    assert held <= eng.pool.band_blocks(1, 16) < eng.pool.blocks_for(70)
    for req, got in zip(reqs, rows):
        feed = np.asarray([req.prompt + req.generated[:-1]])
        with jax.default_matmul_precision("highest"):
            full = np.asarray(ref.logits_fn(weights, jnp.asarray(feed)))[0]
        lo = len(req.prompt) - 1
        assert np.abs(got - full[lo:lo + len(req.generated)]).max() < TOL
        assert req.generated == full[lo:].argmax(-1).tolist()


def test_the_four_shares_add_up_to_the_uncut_layer(weights):
    """held=(0,4) ... (12,4) of 16 experts, the shared expert counted
    once, add up to the layer that holds all 16 (the same router, the
    same top 2)."""
    from paddle_tpu.incubate.nn.moe import DroplessMoE
    rng = np.random.default_rng(3)

    def layer(held):
        pt.seed(0)
        m = DroplessMoE(128, 64, 16, 2, scoring="sigmoid", score_bias=False,
                        norm_topk=True, route_scale=2.5, num_shared=1,
                        init_std=0.2, held=held)
        m.eval()
        return m

    whole = layer(None)
    state = {k: np.asarray(v._array) for k, v in whole.state_dict().items()}
    x = pt.to_tensor(rng.standard_normal((3, 11, 128)).astype("float32"))
    want = np.asarray(whole(x)._array)
    parts = []
    for first in (0, 4, 8, 12):
        part = layer((first, 4))
        cut = dict(state)
        for k in ("w_gate", "w_up", "w_down"):
            cut[k] = state[k][first:first + 4]
        assert part.set_state_dict(cut) == ([], [])
        parts.append(np.asarray(part(x)._array))
    # every share adds the shared expert: counted once
    gate = np.asarray(jax.nn.silu(x._array @ state["shared_gate"]))
    alone = (gate * np.asarray(x._array @ state["shared_up"])) \
        @ state["shared_down"]
    np.testing.assert_allclose(sum(parts) - 3 * alone, want, atol=1e-4)
    assert np.abs(parts[0] - parts[1]).max() > 1e-2
