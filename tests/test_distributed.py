"""Distributed tests on the 8-virtual-CPU mesh (SURVEY §4):
tp == dense, zero stages == unsharded, ring == full, pipeline == serial."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet, mesh as mesh_mod



@pytest.fixture
def mesh_2x2():
    prev = dict(mesh_mod._state)
    mesh_mod.build_mesh(dp=2, pp=1, mp=2)
    yield mesh_mod.get_mesh()
    mesh_mod._state.update(prev)


@pytest.fixture
def mesh_sp4():
    prev = dict(mesh_mod._state)
    mesh_mod.build_mesh(dp=1, pp=1, mp=4)
    yield mesh_mod.get_mesh()
    mesh_mod._state.update(prev)


def test_mesh_build():
    prev = dict(mesh_mod._state)
    m = mesh_mod.build_mesh(dp=2, pp=2, mp=2)
    assert m.shape == {"dp": 2, "pp": 2, "mp": 2}
    assert mesh_mod.degree("mp") == 2
    mesh_mod._state.update(prev)


def test_column_row_parallel_match_dense(mesh_2x2):
    from paddle_tpu.distributed import (ColumnParallelLinear,
                                        RowParallelLinear)
    pt.seed(1)
    col = ColumnParallelLinear(8, 16)
    row = RowParallelLinear(16, 8)
    dense1 = nn.Linear(8, 16)
    dense2 = nn.Linear(16, 8)
    dense1.weight.set_value(col.weight); dense1.bias.set_value(col.bias)
    dense2.weight.set_value(row.weight); dense2.bias.set_value(row.bias)
    x = pt.randn([4, 8])
    np.testing.assert_allclose(row(col(x)).numpy(),
                               dense2(dense1(x)).numpy(), rtol=1e-5)
    assert col.weight.pspec is not None


def test_ring_attention_matches_full(mesh_sp4):
    from paddle_tpu.distributed.ring_attention import ring_attention
    from paddle_tpu.ops.dispatch import call_raw
    np.random.seed(0)
    B, L, H, D = 2, 32, 4, 16
    q, k, v = (jnp.asarray(np.random.randn(B, L, H, D), jnp.float32)
               for _ in range(3))
    for causal in (True, False):
        ring = ring_attention(q, k, v, causal=causal)
        full = call_raw("sdpa", q, k, v, None, is_causal=causal)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                                   atol=2e-5)


def test_pipeline_matches_serial():
    from paddle_tpu.distributed.pipeline import pipeline_apply
    prev = dict(mesh_mod._state)
    mesh = mesh_mod.build_mesh(dp=1, pp=4, mp=1)
    np.random.seed(0)
    D, n_stages, lps = 8, 4, 2
    w = jnp.asarray(np.random.randn(n_stages, lps, D, D) * 0.1, jnp.float32)
    b = jnp.asarray(np.random.randn(n_stages, lps, D) * 0.1, jnp.float32)

    def stage_fn(sp, x):
        def blk(h, lp):
            return jnp.tanh(h @ lp["w"] + lp["b"]), None
        y, _ = jax.lax.scan(blk, x, sp)
        return y

    M, mb = 4, 4
    x = jnp.asarray(np.random.randn(M, mb, D), jnp.float32)
    out = pipeline_apply(stage_fn, {"w": w, "b": b}, x, mesh, n_stages, M)
    ref = x
    for s in range(n_stages):
        for l in range(lps):
            ref = jnp.tanh(ref @ w[s, l] + b[s, l])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    mesh_mod._state.update(prev)


def _tiny_model_and_data(seed=5):
    pt.seed(seed)
    m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
    x = pt.randn([8, 8]); y = pt.randn([8, 8])
    return m, x, y


def _loss_fn(model, xi, yi):
    return F.mse_loss(model(xi), yi)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_match_unsharded(stage):
    prev = dict(mesh_mod._state)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 4, "sharding_stage": stage}
    fleet.init(is_collective=True, strategy=strategy)

    m1, x, y = _tiny_model_and_data()
    m2, _, _ = _tiny_model_and_data()
    m2.set_state_dict(m1.state_dict())

    o1 = pt.optimizer.Adam(learning_rate=0.05, parameters=m1.parameters())
    step = fleet.build_train_step(m1, _loss_fn, o1)
    o2 = pt.optimizer.Adam(learning_rate=0.05, parameters=m2.parameters())

    for _ in range(3):
        dist_loss = step(x, y)
        ref_loss = _loss_fn(m2, x, y)
        ref_loss.backward()
        o2.step(); o2.clear_grad()
        np.testing.assert_allclose(float(dist_loss), float(ref_loss),
                                   rtol=1e-4)
    for (n1, p1), (_, p2) in zip(m1.named_parameters(),
                                 m2.named_parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), rtol=1e-3,
                                   atol=1e-5)
    mesh_mod._state.update(prev)


def test_fleet_gpt_tp_matches_dense():
    """GPT forward with mp=2 sharded weights == same weights dense."""
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    prev = dict(mesh_mod._state)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    pt.seed(11)
    cfg_tp = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                       num_heads=4, max_position_embeddings=32,
                       hidden_dropout=0.0, attention_dropout=0.0,
                       tensor_parallel=True)
    m_tp = GPTForCausalLM(cfg_tp)
    cfg_d = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_position_embeddings=32,
                      hidden_dropout=0.0, attention_dropout=0.0,
                      tensor_parallel=False)
    m_d = GPTForCausalLM(cfg_d)
    m_d.set_state_dict(m_tp.state_dict())
    m_tp.eval(); m_d.eval()
    ids = pt.randint(0, 64, [2, 8])
    np.testing.assert_allclose(m_tp(ids).numpy(), m_d(ids).numpy(),
                               rtol=1e-4, atol=1e-5)
    mesh_mod._state.update(prev)


def _tiny_gpt(tp, seed=13, layers=4, recompute=False):
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    pt.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=layers,
                    num_heads=4, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_recompute=recompute, tensor_parallel=tp)
    return GPTForCausalLM(cfg)


@pytest.mark.parametrize("hybrid", [
    {"dp_degree": 2, "mp_degree": 1, "pp_degree": 2},
    {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2},
    {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
     "sharding_degree": 1, "sharding_stage": 0, "accumulate_steps": 4},
    # interleaved (virtual) pipeline: M > P exercises the inter-chunk FIFO
    {"dp_degree": 2, "mp_degree": 1, "pp_degree": 2,
     "accumulate_steps": 4, "virtual_pp_degree": 2},
    # M == P: zero-delay wrap-around path
    {"dp_degree": 2, "mp_degree": 1, "pp_degree": 2,
     "accumulate_steps": 2, "virtual_pp_degree": 2},
])
def test_fleet_gpt_pipeline_matches_serial(hybrid):
    """pp>1 fleet step == serial eager training (loss + params)."""
    from paddle_tpu.text import gpt_loss_fn
    prev = dict(mesh_mod._state)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(hybrid)
    fleet.init(is_collective=True, strategy=strategy)

    m_pp = _tiny_gpt(tp=hybrid.get("mp_degree", 1) > 1)
    m_ref = _tiny_gpt(tp=False, seed=99)
    m_ref.set_state_dict(m_pp.state_dict())

    o_pp = pt.optimizer.Adam(learning_rate=0.02,
                             parameters=m_pp.parameters())
    step = fleet.build_train_step(m_pp, gpt_loss_fn, o_pp)
    o_ref = pt.optimizer.Adam(learning_rate=0.02,
                              parameters=m_ref.parameters())

    pt.seed(7)
    ids = pt.randint(0, 64, [8, 16])
    labels = pt.randint(0, 64, [8, 16])
    for _ in range(3):
        pp_loss = step(ids, labels)
        ref_loss = gpt_loss_fn(m_ref, ids, labels)
        ref_loss.backward()
        o_ref.step(); o_ref.clear_grad()
        np.testing.assert_allclose(float(pp_loss), float(ref_loss),
                                   rtol=2e-4)
    step.sync_model()
    ref_params = dict(m_ref.named_parameters())
    for n, p in m_pp.named_parameters():
        np.testing.assert_allclose(p.numpy(), ref_params[n].numpy(),
                                   rtol=1e-3, atol=3e-4)
    mesh_mod._state.update(prev)


def test_fleet_gpt_pipeline_with_remat_and_zero():
    """pp + recompute + ZeRO-1 still matches serial losses."""
    from paddle_tpu.text import gpt_loss_fn
    prev = dict(mesh_mod._state)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 2, "sharding_degree": 2,
                               "sharding_stage": 1}
    fleet.init(is_collective=True, strategy=strategy)

    m_pp = _tiny_gpt(tp=False, recompute=True)
    m_ref = _tiny_gpt(tp=False, seed=99)
    m_ref.set_state_dict(m_pp.state_dict())
    o_pp = pt.optimizer.Adam(learning_rate=0.02,
                             parameters=m_pp.parameters())
    step = fleet.build_train_step(m_pp, gpt_loss_fn, o_pp)
    o_ref = pt.optimizer.Adam(learning_rate=0.02,
                              parameters=m_ref.parameters())
    pt.seed(3)
    ids = pt.randint(0, 64, [4, 16])
    labels = pt.randint(0, 64, [4, 16])
    for _ in range(2):
        pp_loss = step(ids, labels)
        ref_loss = gpt_loss_fn(m_ref, ids, labels)
        ref_loss.backward()
        o_ref.step(); o_ref.clear_grad()
        np.testing.assert_allclose(float(pp_loss), float(ref_loss),
                                   rtol=2e-4)
    # state_dict auto-syncs the stacked pp stage params (no explicit
    # sync_model call) — trained block weights must match the reference
    sd = m_pp.state_dict()
    ref = dict(m_ref.named_parameters())
    k = "gpt.h.1.mlp.fc_in.weight"
    np.testing.assert_allclose(sd[k].numpy(), ref[k].numpy(),
                               rtol=1e-3, atol=3e-4)
    mesh_mod._state.update(prev)


def test_collective_api_eager():
    from paddle_tpu import distributed as dist
    t = pt.ones([4])
    dist.all_reduce(t)  # single-process: identity
    np.testing.assert_allclose(t.numpy(), np.ones(4))
    assert dist.get_world_size() >= 1
    assert dist.get_rank() == 0


def test_shard_activation_noop_without_mesh():
    from paddle_tpu.distributed import shard_activation
    prev = dict(mesh_mod._state)
    mesh_mod._state["mesh"] = None
    mesh_mod._state["degrees"] = None
    x = pt.ones([4, 4])
    assert shard_activation(x, (None, None)) is x
    mesh_mod._state.update(prev)


def test_bubble_fraction():
    from paddle_tpu.distributed.pipeline import bubble_fraction
    # GPipe: (P-1)/(M+P-1); interleaving by V shrinks the bubble ~V-fold
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(4, 4, n_chunks=4) == pytest.approx(3 / 19)
    assert bubble_fraction(2, 8, n_chunks=2) == pytest.approx(1 / 17)


def test_interleaved_pipeline_matches_serial_low_level():
    """4 virtual stages on 2 devices (V=2), M=4 microbatches: output must
    equal the serial layer sweep (schedule + chunk layout correctness)."""
    from paddle_tpu.distributed.pipeline import pipeline_apply_hybrid
    prev = dict(mesh_mod._state)
    mesh = mesh_mod.build_mesh(dp=1, pp=2, mp=1)
    np.random.seed(0)
    D, L, P_, V = 8, 8, 2, 2
    lpc = L // (P_ * V)
    w = jnp.asarray(np.random.randn(L, D, D) * 0.1, jnp.float32)
    b = jnp.asarray(np.random.randn(L, D) * 0.1, jnp.float32)

    def block_apply(lp, h, key):
        # round-3 contract: (y, aux scalar) — aux carries MoE router losses
        return jnp.tanh(h @ lp["w"] + lp["b"]), jnp.zeros((), jnp.float32)

    # device p rows: chunk v covers virtual stage v*P+p (lpc layers each)
    order = np.asarray([(j // lpc * P_ + p) * lpc + j % lpc
                        for p in range(P_) for j in range(L // P_)])
    stacked = {"w": w[order].reshape((P_, L // P_, D, D)),
               "b": b[order].reshape((P_, L // P_, D))}
    M, mb = 4, 2
    x = jnp.asarray(np.random.randn(M, mb, D), jnp.float32)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def run(stacked, x, key):
        out, _aux = pipeline_apply_hybrid(block_apply, stacked, x, key,
                                          mesh, n_stages=P_,
                                          n_microbatches=M, n_chunks=V)
        return out

    out = run(stacked, x, key)
    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ w[i] + b[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    mesh_mod._state.update(prev)
