"""Each plain reference against the model it stands beside, at a tiny
size on the CPU: same weights in, same logits, loss, gradients and
optimizer steps out."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import check, traffic
from benchmark.references import adafactor, gpt as ref, training

CFG = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 2,
       "intermediate_size": 256, "vocab_size": 128,
       "initializer_range": 0.02}
SEQ, SEED, LR = 32, 2 ** 31 + 7, 1e-2


def _model(weights):
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=2, intermediate_size=256,
                    max_position_embeddings=SEQ, hidden_dropout=0.0,
                    attention_dropout=0.0, tensor_parallel=False)
    with pt.LazyGuard():
        model = GPTForCausalLM(cfg)
    missing, unexpected = model.set_state_dict(ref.to_program(weights, CFG))
    assert not missing and not unexpected
    return model


@pytest.fixture(scope="module")
def rows():
    return traffic.train_tokens({"batch": 2, "seq": SEQ}, SEED, 128, pool=3)


def test_weights_come_from_the_seed_alone():
    a = ref.init_weights(CFG, SEQ, SEED)
    b = ref.init_weights(CFG, SEQ, SEED)
    c = ref.init_weights(CFG, SEQ, SEED + 1)
    assert a["qkv_w"].dtype == jnp.bfloat16
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["qkv_w"] == c["qkv_w"]).all()
    assert float(jnp.std(a["wte"].astype(jnp.float32))) == \
        pytest.approx(0.02, rel=0.05)


def test_leaf_names_are_the_models_parameters():
    model = _model(ref.init_weights(CFG, SEQ, SEED, dtype=jnp.float32))
    assert sorted(ref.leaf_names(CFG)) == \
        sorted(n for n, _ in model.named_parameters())


def test_gpt_reference_logits_match_the_model(rows):
    w = ref.init_weights(CFG, SEQ, SEED, dtype=jnp.float32)
    model = _model(w)
    model.eval()
    ids = rows[0][:, :-1]
    got = np.asarray(model(pt.to_tensor(ids))._array)
    want = np.asarray(ref.logits_fn(w, jnp.asarray(ids), 2))
    # float32 on both sides: only the summation order differs
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_gpt_reference_loss_matches_the_models(rows):
    from paddle_tpu.text import gpt_loss_fn
    w = ref.init_weights(CFG, SEQ, SEED, dtype=jnp.float32)
    model = _model(w)
    ids, labels = rows[0][:, :-1], rows[0][:, 1:]
    got = float(gpt_loss_fn(model, pt.to_tensor(ids), pt.to_tensor(labels)))
    want = float(ref.loss_fn(w, jnp.asarray(ids), jnp.asarray(labels), 2))
    assert got == pytest.approx(want, rel=1e-6)


def test_first_steps_match_the_programs_fused_step_in_float32(rows):
    """Gradient norms read back from the program's Adafactor state and
    the parameters' change after three steps equal the reference's: the
    plain Adafactor and the program's are the same algorithm."""
    from paddle_tpu.text import gpt_loss_fn
    from benchmark.drivers import train as drv
    w = ref.init_weights(CFG, SEQ, SEED, dtype=jnp.float32)
    model = _model(w)
    opt = pt.optimizer.Adafactor(learning_rate=LR,
                                 parameters=model.parameters())
    step = pt.jit.train_step(model, gpt_loss_fn, opt)
    names = ref.leaf_names(CFG)
    losses = []
    for i in range(3):
        losses.append(float(step(pt.to_tensor(rows[i][:, :-1]),
                                 pt.to_tensor(rows[i][:, 1:]))))
        if i == 0:
            order = [n for n, _ in model.named_parameters()]
            shapes = {n: tuple(p.shape)
                      for n, p in model.named_parameters()}
            state = dict(zip(order, step.state_dict()["opt_state"]))
            gnorm = np.asarray([float(adafactor.grad_norm_from_state(
                state[n], shapes[n])) for n in names])
    before = ref.to_program(w, CFG)
    params = dict(model.named_parameters())
    change = np.asarray([float(jnp.linalg.norm(
        params[n]._array - before[n])) for n in names])

    orig = ref.init_weights
    try:        # the reference stores bfloat16 by default; here float32
        ref.init_weights = lambda c, p, s, dtype=jnp.float32: orig(
            c, p, s, dtype=jnp.float32)
        want = training.first_steps(ref, CFG, SEQ, SEED, rows, LR)
    finally:
        ref.init_weights = orig
    program = {"losses": losses, "grad_norms": gnorm, "change_norms": change}
    numbers, _ = check.training_numbers(program, want)
    for name, value in numbers:
        assert value < 2e-3, (name, value)
    assert drv.CHECK_STEPS == 3


def test_the_fp8_control_is_not_the_reference(rows):
    base = training.first_steps(ref, CFG, SEQ, SEED, rows, LR)
    ctrl = training.first_steps(ref, CFG, SEQ, SEED, rows, LR,
                                precision="fp8")
    numbers = dict(check.training_numbers(ctrl, base)[0])
    assert numbers["loss_step1_gap"] > 1e-5
    assert numbers["grad_norm_gap"] > 1e-2


def test_fp8_rounding_keeps_four_significant_bits():
    x = jnp.asarray([1.0, 1.03, 1.0625, 1.1, -3.3, 1e-8, 0.0, 1000.0])
    got = np.asarray(ref._round_significand(x, 3))
    np.testing.assert_allclose(
        got, [1.0, 1.0, 1.0, 1.125, -3.25, 1e-8, 0.0, 1024.0], rtol=0.04)
    m = np.asarray(jnp.frexp(jnp.asarray(got[got != 0]))[0]) * 16
    assert np.allclose(m, np.round(m))


def test_unknown_precision_is_refused():
    with pytest.raises(ValueError):
        ref._q(jnp.ones(2), "int4")


def test_half_batch_fault_reads_far_off(rows):
    base = training.first_steps(ref, CFG, SEQ, SEED, rows, LR)
    half = training.first_steps(ref, CFG, SEQ, SEED, rows, LR,
                                fault="half_batch")
    numbers = dict(check.training_numbers(half, base)[0])
    assert numbers["grad_norm_gap"] > 0.1


def test_next_token_gaps_are_zero_for_the_references_own_choice(rows):
    w = ref.init_weights(CFG, SEQ, SEED)
    tokens = jnp.asarray(rows[0][:1, :-1])
    _, _, top = ref.next_token_gaps(w, tokens, jnp.zeros(SEQ, jnp.int32), 2)
    best, took, _ = ref.next_token_gaps(w, tokens, top.astype(jnp.int32), 2)
    assert check.widest_logit_gap(best, took) == 0.0
    best, took, _ = ref.next_token_gaps(
        w, tokens, (top.astype(jnp.int32) + 1) % 128, 2)
    assert check.widest_logit_gap(best, took) > 0.0


@pytest.mark.parametrize("program,reference,keep,want", [
    ([1.0, 2.0, 4.0], [1.0, 2.0, 4.0], None, 0.0),
    ([1.0, 2.0, 5.0], [1.0, 2.0, 4.0], None, 0.25),
    # a tiny leaf is measured against the median leaf, not itself
    ([0.002, 2.0, 4.0], [0.001, 2.0, 4.0], None, 0.0005),
    # a leaf that did not move, or moved double, reads about 1
    ([0.0, 2.0, 4.0], [2.0, 2.0, 4.0], None, 1.0),
    ([4.0, 2.0, 4.0], [2.0, 2.0, 4.0], None, 1.0),
    ([9.0, 2.0, 4.0], [1.0, 2.0, 4.0], [False, True, True], 0.0),
    ([float("nan"), 2.0, 4.0], [1.0, 2.0, 4.0], None, float("inf")),
])
def test_worst_leaf_gap(program, reference, keep, want):
    gap, _ = check.worst_leaf_gap(program, reference, keep)
    assert gap == pytest.approx(want)


def test_dead_gradient_leaves_are_left_out_of_the_change():
    ref_r = {"losses": [1.0], "grad_norms": np.asarray([1.0, 1.0, 1e-5]),
             "change_norms": np.asarray([1.0, 1.0, 1e-4])}
    prog = {"losses": [1.0], "grad_norms": np.asarray([1.0, 1.0, 1e-5]),
            "change_norms": np.asarray([1.0, 1.0, 5.0])}
    numbers, where = check.training_numbers(prog, ref_r)
    assert dict(numbers)["change_norm_gap"] == 0.0
    assert where["dead_leaves"] == 1
