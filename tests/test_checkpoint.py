"""Checkpoint/resume + inference export.

Mirrors the reference's io tests (test/legacy_test/test_paddle_save_load.py,
test_jit_save_load.py): deterministic resume equality, state round-trips,
TranslatedLayer replay.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn



def _make(seed=0):
    pt.seed(seed)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = pt.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())
    sched = None
    return m, opt


def _step(m, opt, x, y):
    loss = ((m(x) - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss)


def test_deterministic_resume(tmp_path):
    m, opt = _make()
    x = pt.randn([16, 8])
    y = pt.randn([16, 4])
    for _ in range(3):
        _step(m, opt, x, y)
    pt.save_state(str(tmp_path / "ck"), model=m, optimizer=opt, step=3)
    # branch A: continue directly
    a_losses = [_step(m, opt, x, y) for _ in range(3)]

    # branch B: fresh model+opt, restore, continue — must match exactly
    m2, opt2 = _make(seed=123)  # different init, overwritten by restore
    meta = pt.load_state(str(tmp_path / "ck"), model=m2, optimizer=opt2)
    assert meta["step"] == 3
    b_losses = [_step(m2, opt2, x, y) for _ in range(3)]
    np.testing.assert_allclose(a_losses, b_losses, rtol=1e-6)


def test_checkpoint_scaler_and_extra(tmp_path):
    m, opt = _make()
    scaler = pt.amp.GradScaler(init_loss_scaling=64.0)
    pt.save_state(str(tmp_path / "ck"), model=m, optimizer=opt,
                  scaler=scaler, step=7, extra={"epoch": 2})
    scaler2 = pt.amp.GradScaler(init_loss_scaling=1.0)
    m2, opt2 = _make(seed=9)
    meta = pt.load_state(str(tmp_path / "ck"), model=m2, optimizer=opt2,
                         scaler=scaler2)
    assert scaler2.get_loss_scaling() == 64.0
    assert meta["extra"]["epoch"] == 2


def test_rng_restored(tmp_path):
    m, opt = _make()
    pt.seed(42)
    pt.save_state(str(tmp_path / "ck"), model=m, optimizer=opt)
    r1 = pt.randn([4]).numpy()
    pt.seed(7)  # perturb the stream
    pt.load_state(str(tmp_path / "ck"), model=m, optimizer=opt)
    r2 = pt.randn([4]).numpy()
    np.testing.assert_allclose(r1, r2)


def test_lr_scheduler_in_checkpoint(tmp_path):
    pt.seed(0)
    m = nn.Linear(4, 4)
    sched = pt.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2)
    opt = pt.optimizer.SGD(learning_rate=sched, parameters=m.parameters())
    for _ in range(5):
        sched.step()
    pt.save_state(str(tmp_path / "ck"), model=m, optimizer=opt)
    sched2 = pt.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2)
    m2 = nn.Linear(4, 4)
    opt2 = pt.optimizer.SGD(learning_rate=sched2, parameters=m2.parameters())
    pt.load_state(str(tmp_path / "ck"), model=m2, optimizer=opt2)
    assert sched2.get_lr() == pytest.approx(sched.get_lr())


def test_inconsistent_checkpoint_detected(tmp_path):
    import json
    m, opt = _make()
    path = tmp_path / "ck"
    pt.save_state(str(path), model=m, optimizer=opt, step=1)
    # simulate a crash mid-overwrite: meta from a different save
    meta_file = path / "meta.json"
    meta = json.loads(meta_file.read_text())
    meta["commit_token"] = "00" * 16
    meta_file.write_text(json.dumps(meta))
    m2, opt2 = _make(seed=1)
    with pytest.raises(RuntimeError, match="inconsistent"):
        pt.load_state(str(path), model=m2, optimizer=opt2)


def test_jit_save_load_inference(tmp_path):
    pt.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m.eval()
    path = str(tmp_path / "inf")
    pt.jit.save(m, path, input_spec=[pt.jit.InputSpec([2, 8])])
    x = pt.randn([2, 8])
    want = m(x).numpy()
    tl = pt.jit.load(path)
    got = tl(x).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_jit_save_load_dynamic_batch(tmp_path):
    pt.seed(0)
    m = nn.Linear(8, 4)
    m.eval()
    path = str(tmp_path / "inf_dyn")
    pt.jit.save(m, path, input_spec=[pt.jit.InputSpec([None, 8])])
    tl = pt.jit.load(path)
    for bs in (1, 3, 17):
        x = pt.randn([bs, 8])
        np.testing.assert_allclose(tl(x).numpy(), m(x).numpy(),
                                   rtol=1e-5, atol=1e-6)


def test_jit_save_load_with_buffers(tmp_path):
    pt.seed(0)
    m = nn.Sequential(nn.Linear(8, 8), nn.BatchNorm1D(8))
    x = pt.randn([16, 8])
    m.train()
    m(x)  # populate running stats
    m.eval()
    path = str(tmp_path / "inf_bn")
    pt.jit.save(m, path, input_spec=[pt.jit.InputSpec([4, 8])])
    tl = pt.jit.load(path)
    xe = pt.randn([4, 8])
    np.testing.assert_allclose(tl(xe).numpy(), m(xe).numpy(),
                               rtol=1e-5, atol=1e-6)


def test_generic_pickle_save_load(tmp_path):
    m, _ = _make()
    p = str(tmp_path / "sd.pdparams")
    pt.save(m.state_dict(), p)
    sd = pt.load(p)
    m2, _ = _make(seed=5)
    m2.set_state_dict(sd)
    x = pt.randn([2, 8])
    np.testing.assert_allclose(m2(x).numpy(), m(x).numpy(), rtol=1e-6)


def test_fleet_engine_resume_matches_uninterrupted(tmp_path):
    """Checkpoint/resume THROUGH the fleet engine (pp + dp + Adam state):
    save after 2 steps, rebuild everything, load, continue — losses must
    match an uninterrupted 4-step run exactly."""
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.text import GPTConfig, GPTForCausalLM, gpt_loss_fn
    prev = dict(mesh_mod._state)

    def build():
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                                   "pp_degree": 2, "accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        pt.seed(7)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        tensor_parallel=False)
        m = GPTForCausalLM(cfg)
        opt = pt.optimizer.Adam(learning_rate=0.02,
                                parameters=m.parameters())
        return m, opt, fleet.build_train_step(m, gpt_loss_fn, opt)

    try:
        pt.seed(3)
        ids = pt.randint(0, 64, [4, 16])
        labels = pt.randint(0, 64, [4, 16])

        # uninterrupted 4-step run
        m1, _, step1 = build()
        ref_losses = [float(step1(ids, labels)) for _ in range(4)]

        # interrupted: 2 steps -> save -> rebuild -> load -> 2 more steps
        m2, _, step2 = build()
        for _ in range(2):
            step2(ids, labels)
        pt.save_state(str(tmp_path / "fleet_ck"), model=m2, optimizer=step2)

        m3, _, step3 = build()
        pt.load_state(str(tmp_path / "fleet_ck"), model=m3, optimizer=step3)
        resumed = [float(step3(ids, labels)) for _ in range(2)]
        np.testing.assert_allclose(resumed, ref_losses[2:], rtol=1e-5)
    finally:
        mesh_mod._state.update(prev)


def test_fleet_resume_topology_guards(tmp_path):
    """Wrong-topology or eager-format checkpoints must fail loudly, and a
    save-after-load-before-step round-trip must not drop the moments."""
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.text import GPTConfig, GPTForCausalLM, gpt_loss_fn
    prev = dict(mesh_mod._state)

    def build(vpp):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                                   "pp_degree": 2, "accumulate_steps": 2,
                                   "virtual_pp_degree": vpp}
        fleet.init(is_collective=True, strategy=strategy)
        pt.seed(7)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        tensor_parallel=False)
        m = GPTForCausalLM(cfg)
        opt = pt.optimizer.Adam(learning_rate=0.02,
                                parameters=m.parameters())
        return m, fleet.build_train_step(m, gpt_loss_fn, opt)

    try:
        pt.seed(3)
        ids = pt.randint(0, 64, [4, 16])
        labels = pt.randint(0, 64, [4, 16])
        m1, s1 = build(vpp=2)
        s1(ids, labels)
        pt.save_state(str(tmp_path / "vpp2"), model=m1, optimizer=s1)

        # vpp mismatch -> loud error (stacked rows would be layer-permuted)
        m2, s2 = build(vpp=1)
        with pytest.raises(ValueError, match="topology"):
            pt.load_state(str(tmp_path / "vpp2"), model=m2, optimizer=s2)

        # eager-format checkpoint into a pp engine -> loud error
        pt.seed(7)
        from paddle_tpu.text import GPTConfig as _C
        cfg = _C(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                 max_position_embeddings=32, hidden_dropout=0.0,
                 attention_dropout=0.0, tensor_parallel=False)
        me = GPTForCausalLM(cfg)
        oe = pt.optimizer.Adam(learning_rate=0.02, parameters=me.parameters())
        gpt_loss_fn(me, ids, labels).backward()
        oe.step(); oe.clear_grad()
        pt.save_state(str(tmp_path / "eager"), model=me, optimizer=oe)
        m3, s3 = build(vpp=2)
        with pytest.raises(ValueError, match="non-pp"):
            pt.load_state(str(tmp_path / "eager"), model=m3, optimizer=s3)

        # save-after-load-before-step keeps the loaded moments
        m4, s4 = build(vpp=2)
        pt.load_state(str(tmp_path / "vpp2"), model=m4, optimizer=s4)
        sd = s4.state_dict()
        assert any("__stacked__" in k for k in sd)
        pt.save_state(str(tmp_path / "resaved"), model=m4, optimizer=s4)
        m5, s5 = build(vpp=2)
        pt.load_state(str(tmp_path / "resaved"), model=m5, optimizer=s5)
        l5 = float(s5(ids, labels))
        m6, s6 = build(vpp=2)
        pt.load_state(str(tmp_path / "vpp2"), model=m6, optimizer=s6)
        l6 = float(s6(ids, labels))
        np.testing.assert_allclose(l5, l6, rtol=1e-6)
    finally:
        mesh_mod._state.update(prev)


def test_eager_optimizer_rejects_stacked_checkpoint():
    m = pt.nn.Linear(4, 4)
    opt = pt.optimizer.Adam(learning_rate=0.1, parameters=m.parameters())
    with pytest.raises(ValueError, match="fleet"):
        opt.set_state_dict({"weight/__stacked__/moment1": pt.zeros([2, 4])})
