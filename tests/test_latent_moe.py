"""Latent attention + dropless routed experts (text/deepseek.py,
incubate/nn/moe.py, ops/pallas/latent_paged_attention.py) against the
plain reference benchmark/references/kimi_vl.py, at a small size on the
CPU, on seeded random weights: logits, never sampled tokens."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.incubate.nn import DroplessMoE, moe_dropless, moe_route
from paddle_tpu.ops.nn_kernels import latent_paged_attention_k
from paddle_tpu.ops.pallas import latent_paged_attention as la
from paddle_tpu.tensor import Tensor
from paddle_tpu.text.deepseek import DeepseekV3Config, DeepseekV3ForCausalLM
from benchmark.references import kimi_vl as ref

# 3 layers = 1 dense + 2 MoE, hidden 128, 4 heads of 32 + 16, latent 64,
# 8 experts top 2, 1 shared (the XLA gather serves its decode steps) ...
SMALL = dict(vocab_size=96, hidden_size=128, num_hidden_layers=3,
             num_attention_heads=4, intermediate_size=256, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             moe_intermediate_size=64, n_routed_experts=8,
             n_shared_experts=1, num_experts_per_tok=2,
             first_k_dense_replace=1, routed_scaling_factor=2.446,
             rope_theta=800000.0, rms_norm_eps=1e-5, initializer_range=0.02,
             norm_topk_prob=True)
# ... and one whose rows the pallas kernel takes (8 heads, latent 128)
LANED = dict(SMALL, num_attention_heads=8, kv_lora_rank=128,
             qk_nope_head_dim=16, v_head_dim=16)
POSITIONS = 128


def build(cfg, dtype="float32", seed=5):
    kw = {k: v for k, v in cfg.items()
          if k not in ("num_hidden_layers", "num_attention_heads")}
    with pt.LazyGuard():
        model = DeepseekV3ForCausalLM(DeepseekV3Config(
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            max_position_embeddings=POSITIONS, dtype=dtype, **kw))
    weights = ref.init_weights(cfg, POSITIONS, seed, dtype=jnp.dtype(dtype))
    missing, unexpected = model.set_state_dict(ref.to_program(weights, cfg))
    assert (missing, unexpected) == ([], [])
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
    ids = np.random.default_rng(0).integers(0, 96, (2, 24))
    with pt.no_grad():
        got = np.asarray(model(pt.to_tensor(ids))._array)
    for row, have in zip(ids, got):
        np.testing.assert_allclose(
            have, reference_logits(weights, SMALL, row), atol=2e-5)


def test_parameters_are_born_in_the_configurations_dtype():
    model, _ = build(SMALL, dtype="bfloat16")
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    before = [p._array for p in model.parameters()]
    pt.amp.decorate(models=model, dtype="bfloat16")
    # born so, so nothing is cast and nothing is copied
    assert all(a is p._array for a, p in zip(before, model.parameters()))


def test_a_state_dict_is_assigned_in_several_calls_like_in_one(
        small, monkeypatch):
    from paddle_tpu.nn import layer
    model, weights = small
    other, _ = build(SMALL, seed=6)
    monkeypatch.setattr(layer, "_ASSIGN_BYTES", 1 << 14)
    assert other.set_state_dict(ref.to_program(weights, SMALL)) == ([], [])
    for (name, a), (_, b) in zip(model.named_parameters(),
                                 other.named_parameters()):
        np.testing.assert_array_equal(np.asarray(a._array),
                                      np.asarray(b._array), err_msg=name)


@pytest.mark.parametrize("use_jit", [False, True], ids=["eager", "jit"])
def test_generate_follows_the_reference_argmax(small, use_jit):
    model, weights = small
    prompt = np.random.default_rng(1).integers(0, 96, (1, 9))
    out = np.asarray(model.generate(pt.to_tensor(prompt), max_new_tokens=5,
                                    use_jit=use_jit)._array)[0]
    want = reference_logits(weights, SMALL, out[:-1]).argmax(-1)[8:]
    np.testing.assert_array_equal(out[9:], want)


# ----------------------------------------------------------------- engine
@pytest.mark.parametrize("cfg, pallas, block", [
    (SMALL, None, 4), (LANED, "interpret", 8)], ids=["gather", "kernel"])
def test_served_logits_equal_the_references_full_forward(
        cfg, pallas, block, monkeypatch):
    """Prefill in chunks + decode from the latent pool (absorbed form;
    the XLA gather, or the pallas kernels interpreted) = the reference's
    full forward at every served position."""
    if pallas:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    model, weights = build(cfg)
    eng = serving.LLMEngine(model, num_blocks=160 // block, block_size=block,
                            max_running=4, prefill_chunk=8)
    served, emit = {}, eng._emit

    def keep(req, row, now):
        served.setdefault(req.id, []).append(np.array(row))
        return emit(req, row, now)

    eng._emit = keep
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n) for n in (21, 5, 13, 30, 9, 17)]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, (6, 9, 4, 7, 3, 5))]
    began = time.time_ns()
    eng.run()
    for req, prompt in zip(reqs, prompts):
        fed = np.concatenate([prompt, req.generated[:-1]])
        want = reference_logits(weights, cfg, fed)[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(served[req.id]), want,
                                   atol=5e-5)
    # the root's counts: the latent op's own walk, and the routed layers
    from paddle_tpu.observability import trace
    roots = [s[6] for s in trace.spans() if s[0] == "serving.step"
             and s[6].get("decode_rows")][-8:]
    slots, cols = 4, eng.table_cols
    mine = [s for s in trace.spans() if s[1] >= began]
    chunks = [s[6] for s in mine if s[0] == "serving.prefill"]
    # every chunk touched 2..8 experts in the FIRST of the two routed
    # layers (the last layer's output is dead code in a prefill program,
    # its routing with it), read with the picks of the decode program
    # dispatched next after it
    fed = sum(len(p) - 1 for p in prompts)
    assert sum(c["tokens"] for c in chunks) == fed
    steps = [s[6] for s in sorted(mine, key=lambda s: s[1])
             if s[0] == "serving.step"]
    assert sum(c.get("prefill_moe_assignments", 0) for c in steps) \
        == fed * 2
    touched = sum(c.get("prefill_experts_touched", 0) for c in steps)
    assert 2 * len(chunks) <= touched <= 8 * len(chunks)
    assert not eng._chunk_loads
    # the routed layers' load comes to the host with the picks, in the
    # step AFTER the one that dispatched the rows
    for before, c in zip(steps, steps[1:]):
        assert c.get("moe_assignments", 0) == before["decode_rows"] * 2 * 2
        if before["decode_rows"]:
            assert 2 <= c["experts_touched"] <= min(16, c["moe_assignments"])
    # the blocks describe the program the step dispatched
    for c in roots:
        assert c["kv_blocks_live"] <= c["kv_blocks_walked"]
        if pallas:      # live blocks, and one block for each dead slot
            assert c["kv_blocks_walked"] == c["kv_blocks_live"] \
                + slots - c["decode_rows"]
        else:
            assert c["kv_blocks_walked"] == slots * cols
    assert eng.close() == ([], [])


def test_absorbed_equals_expanded(small):
    """One new token against cached latent rows: W_kvb absorbed into the
    query and behind the softmax gives what K and V materialised from the
    same rows give."""
    model, _ = small
    attn = model.model.layers[1].self_attn
    rng = np.random.default_rng(2)
    width, bs, rows, cols = model.cfg.cache_width, 4, 3, 6
    pool = jnp.asarray(rng.normal(size=(32, bs, width)), jnp.float32)
    table = jnp.asarray(rng.permutation(32)[:rows * cols]
                        .reshape(rows, cols), jnp.int32)
    pos = jnp.asarray([0, 13, 23], jnp.int32)
    x = Tensor._from_array(jnp.asarray(rng.normal(size=(rows, 1, 128)),
                                       jnp.float32))
    T = Tensor._from_array
    with pt.no_grad():
        q = attn._queries(x, T(pos[:, None]))
        absorbed = attn._absorbed(q, T(pool), T(table), T(pos))._array
        gathered = pool[table.reshape(-1)].reshape(rows, cols * bs, width)
        seen = (jnp.arange(cols * bs)[None, :] <= pos[:, None])
        expanded = attn._expanded(
            q, T(gathered), mask=T(seen[:, None, None, :]))._array
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5)


# ----------------------------------------------------------------- kernel
@pytest.mark.parametrize("lens", [
    [1, 48, 17, 0, 33],         # a dead slot, a full table, ragged, empty
    [48, 48, 48, 48, 48],       # every table full
    [1, 1, 1, 1, 1],            # every slot dead
    [5, 9, 44, 2, 31]], ids=["ragged", "full", "dead", "short"])
def test_interpreted_kernel_equals_the_xla_gather(lens):
    rng = np.random.default_rng(0)
    rows, heads, width, value, bs, blocks, cols = 5, 4, 256, 128, 4, 64, 12
    pool = jnp.asarray(rng.normal(size=(blocks, bs, width)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, 1, heads, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks)[:rows * cols]
                         .reshape(rows, cols), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = latent_paged_attention_k(q, pool, tables,
                                    jnp.maximum(lens, 1) - 1, value,
                                    scale=0.07)
    got = la.latent_paged_decode_attention(q, pool, tables, lens, value,
                                           scale=0.07, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_kernels_walk_and_gate():
    assert la.walked_blocks([1, 48, 17, 0, 33], 12, 4) == 1 + 12 + 5 + 1 + 9
    assert la.walked_blocks([4000], 12, 4) == 12    # never past the table
    bf16, f32 = jnp.bfloat16, jnp.float32
    real = ((32, 1, 16, 640), (32000, 16, 640), 512)
    assert la.supports(*real, bf16) and la.supports(*real, f32)
    assert la.supports((1, 8, 16, 640), real[1], 512, bf16)   # a chunk
    assert not la.supports(real[0], real[1], 512, jnp.float16)
    assert not la.supports((32, 1, 16, 576), (32000, 16, 576), 512, bf16)
    assert not la.supports(real[0], real[1], 576, bf16)     # c unaligned
    assert not la.supports(real[0], (32000, 8, 640), 512, bf16)  # bf16 tile
    assert la.supports(real[0], real[1], 512, bf16, mp=2)
    assert not la.supports(real[0], real[1], 512, bf16, mp=4)


# ---------------------------------------------------------------- routing
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_bias_picks_and_never_weighs(scoring):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 3, jnp.float32)
    picked, w = moe_route(x, wg, bias, top_k=3, scoring=scoring,
                          norm_topk=True, route_scale=2.446)
    logits = np.asarray(x) @ np.asarray(wg)
    s = 1 / (1 + np.exp(-logits)) if scoring == "sigmoid" else \
        np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.argsort(-(s + np.asarray(bias)), -1)[:, :3]
    np.testing.assert_array_equal(np.sort(picked, -1), np.sort(want, -1))
    # without the bias other experts would have been picked ...
    assert (np.sort(np.argsort(-s, -1)[:, :3], -1)
            != np.sort(want, -1)).any()
    # ... and the weights are the picked SCORES, normalised, times the scale
    sel = np.take_along_axis(s, np.asarray(picked), -1)
    np.testing.assert_allclose(w, sel / sel.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-5)
    unscaled = moe_route(x, wg, bias, top_k=3, scoring=scoring,
                         norm_topk=False)[1]
    np.testing.assert_allclose(unscaled, sel, rtol=1e-5)


def _layer(bias):
    """The small model's first expert layer with its selection bias set,
    and that layer's reference leaves."""
    model, weights = build(SMALL)
    layer = model.model.layers[1].mlp
    layer.score_bias._inplace_assign(jnp.asarray(bias, jnp.float32))
    lp = dict(weights["layers"][1], bias=jnp.asarray(bias, jnp.float32))
    return layer, lp, weights.hyper


@pytest.mark.parametrize("bias", [
    np.zeros(8), np.linspace(-0.2, 0.2, 8),
    # all tokens to the same two experts, whatever their scores
    np.array([0, 0, 0, 9.0, 0, 9.0, 0, 0])],
    ids=["no-bias", "spread", "all-to-two-experts"])
def test_dropless_layer_equals_the_reference_under_any_imbalance(bias):
    layer, lp, hyper = _layer(bias)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 37, 128)),
                    jnp.float32)
    live = np.arange(74) % 3 != 0
    with pt.no_grad():
        y, load = layer(Tensor._from_array(x),
                        live=Tensor._from_array(jnp.asarray(live)))
    want, _ = ref._experts(x.reshape(74, 128), ref._f32(lp), hyper,
                           "float32")
    np.testing.assert_allclose(np.asarray(y._array).reshape(74, 128),
                               np.asarray(want), atol=2e-5)
    # every assignment of a live token is counted: nothing is dropped
    load = np.asarray(load._array)
    assert load.sum() == live.sum() * 2
    if bias.max() > 1:
        assert load[3] == load[5] == live.sum() and load.sum() == load[[3, 5]].sum()


def test_grouped_products_leave_no_assignment_out():
    """`moe_dropless` against a loop over the experts, every token to
    expert 2 first and 0 second."""
    rng = np.random.default_rng(5)
    n, d, f, e = 19, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
    picked = jnp.tile(jnp.asarray([[2, 0]], jnp.int32), (n, 1))
    w = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    got = moe_dropless(x, picked, w, wg, wu, wd)
    want = sum(w[:, j:j + 1] * ((jax.nn.silu(x @ wg[i]) * (x @ wu[i]))
                                @ wd[i]) for j, i in enumerate((2, 0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("m, g, k, n", [(37, 5, 128, 256),
                                        (300, 8, 256, 128)],
                         ids=["padded-rows", "three-row-tiles"])
def test_interpreted_grouped_kernel_equals_the_ragged_product(
        m, g, k, n, monkeypatch):
    from paddle_tpu.ops.dispatch import call_raw
    rng = np.random.default_rng(6)
    sizes = jnp.asarray(rng.multinomial(m, np.ones(g) / g), jnp.int32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
    want = call_raw("grouped_matmul", x, w, sizes)       # ragged_dot
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got = call_raw("grouped_matmul", x, w, sizes)        # the kernel
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_the_grouped_kernels_tiles():
    from paddle_tpu.ops.pallas import _gmm_tiling
    # the cell's experts: a whole matrix is one block of 5.8 MB
    assert _gmm_tiling(2048, 1408, 2) == (128, 2048, 1408)
    assert _gmm_tiling(1408, 2048, 2) == (128, 1408, 2048)
    # a wider expert: n split into lane multiples that divide it
    assert _gmm_tiling(4096, 14336, 2) == (128, 4096, 512)
    assert _gmm_tiling(2048, 1400, 2) is None       # not whole lane tiles
    assert _gmm_tiling(64, 128, 4) is None


def test_the_layer_refuses_more_picks_than_experts():
    with pytest.raises(ValueError):
        DroplessMoE(16, 8, num_experts=2, top_k=3)
