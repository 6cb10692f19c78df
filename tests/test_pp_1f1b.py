"""1F1B pipeline schedule (VERDICT r3 item 4; reference: fleet
meta_parallel pipeline_parallel.py's 1F1B).

The 1F1B path is a hand-written two-scan custom_vjp (pipeline.py
onef1b_pipeline): forward GPipe wave storing only [M, mb] stage-boundary
inputs, backward wave recomputing each stage with jax.vjp.  These tests
pin (a) exact-math parity with the differentiable GPipe scan across
pp degrees, MoE, and dp composition, and (b) the memory claim: compiled
temp bytes strictly below the GPipe scan's and below the 1F1B analytic
activation budget."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod



@pytest.fixture
def restore_mesh():
    prev = dict(mesh_mod._state)
    yield
    mesh_mod._state.update(prev)


def _gpt(seed=0, layers=4, moe=False):
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    pt.seed(seed)
    kw = {}
    if moe:
        kw = dict(num_experts=4, moe_capacity_factor=4.0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=layers,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    tensor_parallel=False, **kw)
    return GPTForCausalLM(cfg)


def _train(sched, pp, M, dp=1, moe=False, steps=3, seed=0, layers=4,
           vpp=1):
    """Build + train a few steps under `sched`; return (losses, state)."""
    from paddle_tpu.text import gpt_loss_fn
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": 1,
                               "pp_degree": pp, "accumulate_steps": M,
                               "pp_schedule": sched,
                               "virtual_pp_degree": vpp}
    fleet.init(is_collective=True, strategy=strategy)
    m = _gpt(seed=seed, layers=layers, moe=moe)
    opt = pt.optimizer.Adam(learning_rate=0.02, parameters=m.parameters())
    step = fleet.build_train_step(m, gpt_loss_fn, opt)
    pt.seed(7)
    ids = pt.randint(0, 64, [8, 16])
    labels = pt.randint(0, 64, [8, 16])
    losses = [float(step(ids, labels)) for _ in range(steps)]
    step.sync_model()
    sd = {k: np.asarray(v._array) for k, v in m.state_dict().items()}
    return losses, sd


def _assert_parity(restore_mesh, pp, M, dp=1, moe=False, layers=4,
                   vpp=1):
    prev = dict(mesh_mod._state)
    l_ref, sd_ref = _train("F-then-B", pp, M, dp=dp, moe=moe,
                           layers=layers, vpp=1)
    mesh_mod._state.update(prev)
    l_1f, sd_1f = _train("1F1B", pp, M, dp=dp, moe=moe, layers=layers,
                         vpp=vpp)
    assert np.allclose(l_ref, l_1f, rtol=3e-4, atol=3e-5), \
        f"loss mismatch: {l_ref} vs {l_1f}"
    worst = max(float(np.max(np.abs(sd_ref[k] - sd_1f[k])))
                for k in sd_ref)
    assert worst < 5e-4, f"param divergence {worst}"


def test_1f1b_matches_gpipe_pp2(restore_mesh):
    _assert_parity(restore_mesh, pp=2, M=4)


def test_1f1b_matches_gpipe_pp4(restore_mesh):
    _assert_parity(restore_mesh, pp=4, M=4, layers=8)


def test_1f1b_matches_gpipe_moe(restore_mesh):
    """Router aux losses (and their gradients) ride the custom bwd via the
    daux cotangent — parity must hold including the aux term."""
    _assert_parity(restore_mesh, pp=2, M=2, moe=True)


def test_1f1b_matches_gpipe_dp_x_pp(restore_mesh):
    """dp stays a GSPMD annotation inside the partial-manual shard_map in
    both the forward AND the hand-written backward."""
    _assert_parity(restore_mesh, pp=2, M=2, dp=2)


def test_interleaved_1f1b_matches_gpipe(restore_mesh):
    """vpp=2 x 1F1B (Megatron's interleaved 1F1B as a two-scan
    custom_vjp): chunk waves + mirrored grad FIFO must reproduce the
    plain differentiable schedule's math exactly."""
    _assert_parity(restore_mesh, pp=2, M=4, layers=4, vpp=2)


def test_interleaved_1f1b_matches_gpipe_pp2_vpp2_deep(restore_mesh):
    _assert_parity(restore_mesh, pp=2, M=4, layers=8, vpp=2)


def test_1f1b_is_default_schedule(restore_mesh):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 2, "accumulate_steps": 4}
    fleet.init(is_collective=True, strategy=strategy)
    from paddle_tpu.text import gpt_loss_fn
    m = _gpt()
    opt = pt.optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
    step = fleet.build_train_step(m, gpt_loss_fn, opt)
    assert step.pp_schedule == "1F1B"
    # vpp>1 also defaults to 1F1B (interleaved wave); F-then-B on request
    strategy2 = fleet.DistributedStrategy()
    strategy2.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                "pp_degree": 2, "accumulate_steps": 4,
                                "virtual_pp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy2)
    m2 = _gpt()
    opt2 = pt.optimizer.SGD(learning_rate=0.01, parameters=m2.parameters())
    step2 = fleet.build_train_step(m2, gpt_loss_fn, opt2)
    assert step2.pp_schedule == "1F1B"
    strategy3 = fleet.DistributedStrategy()
    strategy3.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                "pp_degree": 2, "accumulate_steps": 4,
                                "pp_schedule": "F-then-B"}
    fleet.init(is_collective=True, strategy=strategy3)
    m3 = _gpt()
    opt3 = pt.optimizer.SGD(learning_rate=0.01, parameters=m3.parameters())
    step3 = fleet.build_train_step(m3, gpt_loss_fn, opt3)
    assert step3.pp_schedule == "FTHENB"


def test_1f1b_full_step_memory_below_gpipe(restore_mesh):
    """Whole fused train step: 1F1B's compiled temp bytes must undercut
    the differentiable GPipe scan's at the same config."""
    from paddle_tpu.text import gpt_loss_fn
    P, M = 2, 8
    hidden, seq, batch, layers, heads = 64, 64, 16, 4, 4
    temps = {}
    for sched in ("F-then-B", "1F1B"):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": P, "accumulate_steps": M,
                                   "pp_schedule": sched}
        fleet.init(is_collective=True, strategy=strategy)
        from paddle_tpu.text import GPTConfig, GPTForCausalLM
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_position_embeddings=seq, hidden_dropout=0.0,
                        attention_dropout=0.0, use_recompute=True,
                        tensor_parallel=False)
        m = GPTForCausalLM(cfg)
        opt = pt.optimizer.SGD(learning_rate=0.01,
                               parameters=m.parameters())
        step = fleet.build_train_step(m, gpt_loss_fn, opt)
        ids = pt.randint(0, 128, [batch, seq])
        temps[sched] = step.memory_stats(ids, ids).temp_size_in_bytes
    assert temps["1F1B"] < temps["F-then-B"], temps


def test_1f1b_region_memory_within_budget(restore_mesh):
    """Pipeline REGION only (what the 1F1B analytic activation budget
    describes — no embed/head/optimizer): temp bytes <= 1.2x the
    P-microbatch budget, and below the GPipe scan's region bytes
    (docs/pp_memory.md methodology; VERDICT r3 item 4 'done' bar)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mm
    from paddle_tpu.distributed.pipeline import (pipeline_apply_1f1b,
                                                 pipeline_apply_hybrid)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 2, "accumulate_steps": 8}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = mm.get_mesh()
    P_, M, H, S, mb, lps = 2, 8, 128, 128, 2, 2

    def block(params, h, key):
        hn = h - h.mean(-1, keepdims=True)
        h = h + jax.nn.gelu(hn @ params["w1"]) @ params["w2"]
        return h, jnp.zeros((), jnp.float32)

    k0 = jax.random.PRNGKey(0)
    stacked = {"w1": 0.02 * jax.random.normal(k0, (P_, lps, H, 4 * H)),
               "w2": 0.02 * jax.random.normal(k0, (P_, lps, 4 * H, H))}
    x_mb = jax.random.normal(jax.random.fold_in(k0, 1), (M, mb, S, H))

    temps = {}
    for sched in ("F-then-B", "1F1B"):
        def loss(st, x, key):
            if sched == "1F1B":
                y, aux = pipeline_apply_1f1b(
                    jax.checkpoint(block), st, x, key, mesh,
                    n_stages=P_, n_microbatches=M)
            else:
                y, aux = pipeline_apply_hybrid(
                    jax.checkpoint(block), st, x, key, mesh,
                    n_stages=P_, n_microbatches=M, n_chunks=1)
            return jnp.sum(y * y) + aux

        g = jax.jit(jax.grad(loss))
        temps[sched] = g.lower(stacked, x_mb, k0).compile(
        ).memory_analysis().temp_size_in_bytes
    act = mb * S * H * 4
    # this block holds ~6 activation tensors per layer (hn, h@w1 x4-wide
    # counts 4, gelu, out) — use the same x12 multiplier methodology as
    # tools/pp_memory.py for a conservative budget
    f1b_budget = P_ * lps * 12 * act
    assert temps["1F1B"] <= 1.2 * f1b_budget, (temps, f1b_budget)
    assert temps["1F1B"] < temps["F-then-B"], temps
