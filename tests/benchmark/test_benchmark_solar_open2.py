"""The cell `solar-open2-250b.reason-overload`: its configuration, its
reference, its counts, its readers on synthetic runs, and a rehearsal of
the run itself (tiny sizes, the CPU): sound is `correct`, an altered
token is not."""
import json
import os
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_hybrid as fh
from benchmark import harness
from benchmark.drivers import serve
from benchmark.references import solar_open2 as ref

CELL = "solar-open2-250b.reason-overload"
SEED = 2 ** 31 + 132
CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "solar-open2-250b.json")))
# the catalog row Solar-Open2-250B of the model-configs guide: every
# number of its `config`, typed in by hand
PUBLISHED = dict(
    partial_rotary_factor=1, hidden_size=4096, num_hidden_layers=48,
    num_attention_heads=64, head_dim=128, num_key_value_heads=8,
    vocab_size=196608, intermediate_size=10240, moe_intermediate_size=1280,
    rms_norm_eps=1e-05, rope_theta=10000, max_position_embeddings=1048576,
    first_k_dense_replace=0, gqa_interval=3, n_routed_experts=320,
    n_shared_experts=1, routed_scaling_factor=1, num_experts_per_tok=8)
GROUPS = dict(
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                        "num_heads": 64, "num_kv_heads": None},
    gqa_layers=[0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    model_type="solar_open2", tie_word_embeddings=False, use_rope=False,
    use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
    norm_topk_prob=True)
SMALL = dict(CONFIG, **CONFIG["rehearsal"])


# ------------------------------------------------------- the configuration
def test_the_file_holds_every_published_number_but_the_four_cuts():
    cuts = ["num_hidden_layers", "n_routed_experts", "vocab_size",
            "max_position_embeddings"]
    assert CONFIG["reduced"] == cuts
    for key, value in PUBLISHED.items():
        if key in cuts:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    for key, value in GROUPS.items():
        assert CONFIG[key] == value, key
    assert [CONFIG[k] for k in cuts] == [4, 40, 24576, 8192]
    # the share: the router keeps the published 320 outputs and its top 8
    assert CONFIG["router_experts"] == PUBLISHED["n_routed_experts"]
    kw = CONFIG["model_kwargs"]
    assert (kw["n_routed_experts"], kw["held_experts"],
            kw["num_experts_per_tok"]) == (320, [0, 40], 8)
    assert 24576 * 8 == PUBLISHED["vocab_size"] and 40 * 8 == 320
    assert "8 chips share each layer" in CONFIG["cut"]["deployment"]
    # no width differs from the source
    assert (kw["num_kv_heads"], kw["head_dim"], kw["kda_num_heads"],
            kw["kda_head_dim"], kw["moe_intermediate_size"],
            kw["short_conv_kernel_size"]) == (8, 128, 64, 128, 1280, 4)
    assert kw["dtype"] == "bfloat16"


def test_the_model_builds_born_bfloat16_and_takes_the_references_names():
    model = harness.build_model(SMALL)
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    harness.load_weights(model, ref, SMALL, SEED)      # a miss is refused
    assert sorted(ref.to_program(ref.init_weights(SMALL, 128, SEED),
                                 SMALL)) == sorted(model.state_dict())
    assert [b.kind for b in model.model.layers] == ["gqa", "kda", "kda",
                                                    "kda"]
    assert model.model.layers[0].mlp.w_gate.shape == [4, 128, 64]
    assert model.model.layers[0].mlp.gate_weight.shape == [128, 16]


# -------------------------------------------------------------- the counts
W = 64 * 128                                        # a KDA layer's width
@pytest.mark.parametrize("got, want", [
    # GQA mixer: q 4096 x 8192, k and v 4096 x 1024, o, the gate
    (fh.gqa_params(CONFIG), 3 * 4096 * 8192 + 2 * 4096 * 1024),
    # KDA mixer: q, k, v, o; two rank-128 pairs; beta; 4 taps x 3 x 8192
    (fh.kda_params(CONFIG),
     4 * 4096 * W + 2 * (4096 * 128 + 128 * W) + 4096 * 64 + 3 * W * 4),
    (fh.kda_out_params(CONFIG), W * 4096 + 4096 * 128 + 128 * W),
    (fh.expert_params(CONFIG), 3 * 4096 * 1280),
    (fh.every_token_expert_params(CONFIG), 4096 * 320 + 3 * 4096 * 1280),
    (fh.head_params(CONFIG), 4096 * 24576),
    (fh.gqa_layers(CONFIG), [0]),
    (fh.kda_layers(CONFIG), 3),
    (fh.kda_state_flops_per_token(CONFIG), 7.0 * 64 * 128 * 128),
    (fh.attention_flops_per_pair(CONFIG), 4.0 * 64 * 128),
    (fh.visible_pairs(4, 10), 10 + 11 + 12 + 13 + 4),
    (fh.kv_bytes_per_token(CONFIG), 2 * 8 * 128 * 2),   # ONE layer's K, V
    (fh.state_bytes_per_row(CONFIG),
     3 * (64 * 128 * 128 * 4 + 3 * 3 * W * 2)),
    (fh.gqa_decode_bytes(CONFIG, 1000), 1000.0 * 8 * 128 * 2 * 2 * 1),
    (fh.kda_step_work(CONFIG, 10),
     (7.0 * 64 * 128 * 128 * 3 * 10,
      (2 * 64 * 128 * 128 * 4 + 6 * 64 * 128 * 4) * 3.0 * 10)),
    (fh.held_expert_work(CONFIG, 100, 30),
     (2.0 * 15728640 * 100,
      30.0 * 15728640 * 2 + 100.0 * 3 * (4096 + 1280) * 2))])
def test_counts_against_hand_arithmetic(got, want):
    assert got == want


def test_the_cut_weighs_what_the_configuration_says():
    assert round(fh.held_params(CONFIG) / 1e6) == 3308
    assert round(fh.held_params(CONFIG) * 2 / 1e9, 2) == 6.62
    layer = fh.every_token_expert_params(CONFIG) \
        + 40 * fh.expert_params(CONFIG)
    assert round((fh.gqa_params(CONFIG) + layer) / 1e6, 1) == 755.2
    assert round((fh.kda_params(CONFIG) + layer) / 1e6, 1) == 783.9
    # whole, a layer holds 320 experts: 10.3 GB (GQA) or 10.4 (KDA), so
    # no chip holds two
    whole = fh.every_token_expert_params(CONFIG) \
        + 320 * fh.expert_params(CONFIG)
    assert round((fh.gqa_params(CONFIG) + whole) * 2 / 1e9, 1) == 10.3
    assert round((fh.kda_params(CONFIG) + whole) * 2 / 1e9, 1) == 10.4


def test_serve_flops_count_what_a_token_really_multiplies():
    decode = fh.gqa_params(CONFIG) + 3 * fh.kda_params(CONFIG) \
        + 4 * fh.every_token_expert_params(CONFIG) + fh.head_params(CONFIG)
    assert fh.decode_token_params(CONFIG) == decode
    # a prompt token: no head; of the last (KDA) layer the mixer up to
    # the recurrence, and no expert layer
    chunk = decode - fh.head_params(CONFIG) - fh.kda_out_params(CONFIG) \
        - fh.every_token_expert_params(CONFIG)
    assert fh.prefill_token_params(CONFIG) == chunk
    state = 7.0 * 64 * 128 * 128 * 3
    assert fh.serve_flops(CONFIG, 512, 0, 0, 0, 0) \
        == (2.0 * chunk + state) * 512
    assert fh.serve_flops(CONFIG, 0, 64, 0, 0, 0) \
        == (2.0 * decode + state) * 64
    # attention in the ONE GQA layer; local assignments, not 8 a token
    assert fh.serve_flops(CONFIG, 0, 0, 1000, 0, 0) == 32768.0 * 1000
    assert fh.serve_flops(CONFIG, 0, 0, 0, 1000, 0) == 32768.0 * 1000
    assert fh.serve_flops(CONFIG, 0, 0, 0, 0, 7) == 2.0 * 15728640 * 7


# ----------------------------------------------------------- the reference
@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)


def test_weights_follow_the_seed_and_the_share_is_in_their_shapes(weights):
    again = ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)
    other = ref.init_weights(SMALL, 128, SEED + 1, dtype=jnp.float32)
    lp = weights["layers"][1]
    assert np.array_equal(lp["e_up"], again["layers"][1]["e_up"])
    assert not np.array_equal(lp["e_up"], other["layers"][1]["e_up"])
    assert float(jnp.abs(lp["bias"]).min()) > 0
    # the selection bias is one realisation for every seed: it sets how
    # many of the held experts a step touches, the amount of work
    assert np.array_equal(lp["bias"], other["layers"][1]["bias"])
    assert not np.array_equal(lp["bias"], weights["layers"][2]["bias"])
    assert lp["router"].shape == (128, 16) and lp["e_up"].shape[0] == 4
    assert "wq" in weights["layers"][0] and "wqkv" in lp
    # A_log = log U(1, 16), dt_bias = softplus^-1 U(1e-3, 0.1)
    assert 0 <= float(lp["a_log"].min()) and float(lp["a_log"].max()) \
        <= np.log(16) + 1e-6
    dt = np.log1p(np.exp(np.asarray(lp["dt_bias"], np.float64)))
    assert 1e-3 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


def test_blocks_of_rows_change_no_number(weights, monkeypatch, capsys):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 64)))
    whole = np.asarray(ref.logits_fn(weights, ids, 4))[0]
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    chosen = np.zeros(64, np.int32)
    chosen[10:50] = np.random.default_rng(1).integers(1, 512, 40)
    best, took, arg = ref.next_token_gaps(weights, ids, jnp.asarray(chosen),
                                          4, "float32")
    tied = np.asarray(ref.undecided.__wrapped__(weights, ids, 4))
    assert 0 < tied.sum() < 64
    np.testing.assert_allclose(best, whole.max(-1), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(took)[~tied], whole[np.arange(64), chosen][~tied],
        atol=2e-6)
    np.testing.assert_array_equal(np.asarray(took)[tied],
                                  np.asarray(best)[tied])
    np.testing.assert_array_equal(arg, whole.argmax(-1))
    # the share left uncompared is said aloud, over the served positions
    assert (f"40 served positions, {tied[10:50].sum()} within ROUTE_TIE"
            in capsys.readouterr().err)


@pytest.mark.parametrize("control", ["fp8", "fp8:router", "fp8:state",
                                     "fp8:experts", "bfloat16"])
def test_the_fp8_control_is_another_computation(weights, control):
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 32)))
    exact = np.asarray(ref.logits_fn(weights, ids, 4))
    other = np.asarray(ref.logits_fn(weights, ids, 4, control))
    whole = np.asarray(ref.logits_fn(weights, ids, 4, "fp8"))
    assert 1e-4 < np.abs(exact - other).max() < 1.0
    # one part alone, or a finer significand, moves less than all in fp8
    assert control == "fp8" or np.abs(exact - other).mean() \
        < np.abs(exact - whole).mean()
    with pytest.raises(ValueError):
        ref.logits_fn(weights, ids, 4, "int4")
    with pytest.raises(ValueError):
        ref.logits_fn(weights, ids, 4, "fp8:latent")


# ------------------------------------------------------------- the readers
OFFSET = 1_790_000_000_123_456_789
MS = 1_000_000
# (step ms, rows, live blocks, held experts touched, prefill (tokens, ctx))
STEPS = [(20.0, 60, 4000, 120, (512, 0)), (14.0, 62, 4100, 125, (256, 512)),
         (30.0, 0, 0, None, (512, 768)), (12.2, 64, 4200, 128, None),
         (15.0, 64, 4300, 130, (64, 0)), (13.0, 64, 4300, 126, None)]
N_QUIET, N_TRACED = 3, 2
STATE_ROW = 3 * (64 * 128 * 128 * 4 + 3 * 3 * W * 2)


def synthetic(counted=True):
    recs, hs = [], []
    t = OFFSET - 400 * MS
    for i, (ms, rows, live, touched, chunk) in enumerate(STEPS):
        counts = {"decode_rows": rows, "kv_blocks_live": live,
                  "kv_blocks_walked": live + 3}
        if counted and touched is not None:
            # a row's 8 picks in 4 layers, of which 1 in 8 is held; a
            # chunk's in the 3 layers whose experts run
            counts.update(
                moe_assignments=rows * 4, moe_assignments_routed=rows * 32,
                experts_touched=touched,
                prefill_moe_assignments=chunk[0] * 3 if chunk else 0,
                prefill_moe_assignments_routed=chunk[0] * 24 if chunk
                else 0,
                prefill_experts_touched=118 if chunk else 0,
                state_slots_live=rows,
                state_bytes_rw=2 * STATE_ROW * rows)
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if chunk:
            recs.append(("serving.prefill", t + MS, t + 9 * MS, 2000 + i,
                         1000 + i, 7, dict(tokens=chunk[0], ctx=chunk[1]),
                         "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [14.0] * N_QUIET,
            "quiet_s": 0.06, "chips": 1, "config": CONFIG,
            "mix": {"engine": {"block_size": 16}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"devices": {}, "spans": hs}}


def test_hybrid_serve_mfu_from_the_spans_own_counts():
    read = harness.load_reader("hybrid_serve_mfu.reason")
    # quiet steps 1..3
    prefilled, decoded = 256 + 512, 62 + 64
    chunks = fh.visible_pairs(256, 512) + fh.visible_pairs(512, 768)
    pairs = (4100 - 31) * 16 + (4200 - 32) * 16
    local = 62 * 4 + 256 * 3 + 64 * 4
    want = fh.serve_flops(CONFIG, prefilled, decoded, chunks, pairs, local) \
        / 0.06 / 197e12
    assert read(synthetic()) == pytest.approx(100.0 * want)
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None       # the parent
    assert read(dict(synthetic(), quiet_s=0)) is None
    assert read({"program_spans": None}) is None


def test_held_assignment_and_state_cache_shares():
    held = harness.load_reader("held_assignment_share.reason")
    assert held(synthetic()) == pytest.approx(12.5)
    assert held(synthetic(counted=False)) is None
    cache = harness.load_reader("state_cache_share.reason")
    state = STATE_ROW * (62 + 64)
    kv = (4100 + 4200) * 16 * 4096      # 4 KB a token: one layer's K and V
    assert cache(synthetic()) == pytest.approx(100.0 * state / (state + kv))
    assert 0 < cache(synthetic()) < 100
    assert cache(synthetic(counted=False)) is None


def test_kda_step_roofline_reads_the_kernel_by_its_name(capsys):
    read = harness.load_reader("kda_step_roofline.reason")
    run = dict(synthetic(), metric="kda_step_roofline.reason")
    # the traced steps are the last two, 64 rows each; three KDA layers a
    # step, 1.0 ms a layer
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:kda_decode_step.%d" % i, i * 2e6, 1.0e6)
        for i in range(6)] + [("mosaic:gmm.1", 20e6, 9e6)], "modules": []}}
    flops_, bytes_ = fh.kda_step_work(CONFIG, 128)
    least = bytes_ / 819e9
    assert least > flops_ / 197e12                     # memory binds
    assert read(run) == pytest.approx(100.0 * least / 6e-3)
    assert 0 < read(run) < 100
    assert "memory binds" in capsys.readouterr().out
    run["trace"]["devices"][0]["ops"] = [("fusion.1", 0.0, 9e6)]
    assert read(run) is None                      # nothing matched: nothing
    assert read(dict(synthetic(counted=False), metric="x")) is None


def test_held_expert_roofline_counts_the_held_experts_alone(capsys):
    read = harness.load_reader("held_expert_matmul_roofline.reason")
    run = dict(synthetic(), metric="held_expert_matmul_roofline.reason")
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:gmm.3", 0.0, 16e6), ("mosaic:gmm.11", 17e6, 14e6),
        ("mosaic:kda_decode_step.3", 32e6, 5e6)], "modules": []}}
    assignments = 2 * 64 * 4 + 64 * 3
    flops_, bytes_ = fh.held_expert_work(CONFIG, assignments,
                                         130 + 126 + 118)
    least = max(flops_ / 197e12, bytes_ / 819e9)
    assert read(run) == pytest.approx(100.0 * least / 30e-3)
    assert 0 < read(run) < 100
    assert "memory binds" in capsys.readouterr().out
    run["trace"]["devices"][0]["ops"] = [("mosaic:ragged-dot-none.1", 0, 9e6)]
    assert read(run) is None
    assert read(dict(synthetic(counted=False), metric="x")) is None


def test_gqa_paged_roofline_counts_one_layer_in_four():
    read = harness.load_reader("gqa_paged_roofline.reason")
    run = dict(synthetic(), traced_context_sum=2_000_000)
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:paged_decode_attention.1", 0.0, 12e6),
        ("mosaic:paged_decode_attention.1", 15e6, 13e6),
        ("mosaic:latent_paged_decode_attention.2", 30e6, 50e6)],
        "modules": []}}
    need = 2_000_000 * 4096.0           # NOT x num_hidden_layers
    assert read(run) == pytest.approx(100.0 * need / 819e9 / 25e-3)
    assert 0 < read(run) < 100
    run["trace"]["devices"][0]["ops"] = [
        ("mosaic:latent_paged_decode_attention.2", 0.0, 9e6)]
    assert read(run) is None
    assert read(dict(run, traced_context_sum=0)) is None


# ------------------------------------------------------------------ the run
def _args(build=None, seconds=2.0):
    return types.SimpleNamespace(seed=SEED, seconds=seconds, trace=0,
                                 rehearse=True, build=build)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, rehearse=True)


def test_the_cell_reports_what_the_issue_names():
    real = harness.load_cell(CELL)
    assert real["cell"]["chips"] == 1
    assert [m["name"] for m in real["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    names = [m["name"] for m in real["per_layer"]]
    assert names[:6] == [
        "hybrid_serve_mfu.reason", "kda_step_roofline.reason",
        "held_expert_matmul_roofline.reason", "gqa_paged_roofline.reason",
        "held_assignment_share.reason", "state_cache_share.reason"]
    # another model's counts stay out
    for other in ("serve_mfu", "moe_serve_mfu", "paged_roofline",
                  "expert_matmul_roofline", "experts_touched_share",
                  "latent_paged_roofline", "idle_explained_share"):
        assert other + ".reason" not in names
    assert len(names) == 16 and all(
        n.endswith(".reason") and callable(harness.load_reader(n))
        for n in names)
    eng = real["mix"]["engine"]
    assert (eng["max_running"], eng["block_size"], eng["prefill_chunk"]) \
        == (64, 16, 512)
    assert real["mix"]["prompt_tokens"]["max"] \
        + real["mix"]["output_tokens"]["max"] == 8192 \
        == real["config"]["max_position_embeddings"]
    assert len(real["cell"]["why"]) <= 200


def test_sound_rehearsal_run_is_correct(spec):
    out = serve.run(spec, _args(), time.perf_counter(), {})
    over = [n for n, v, lim in out["checks"] if not harness.within(v, lim)]
    assert not over, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["e2e"]["serve_tokens_per_s"] > 0


class _AlteredToken(serve.ServeCell):
    """Every fifth logits row has its best token pushed to the bottom
    before the engine samples."""

    def __init__(self, spec, seed):
        super().__init__(spec, seed)
        emit, count = self.eng._emit, [0]

        def altered(req, row, now):
            count[0] += 1
            if count[0] % 5 == 0:
                row = np.array(row)
                row[int(np.argmax(row))] = row.min() - 1.0
            return emit(req, row, now)
        self.eng._emit = altered


def test_an_altered_token_is_not_correct(spec):
    out = serve.run(spec, _args(build=_AlteredToken), time.perf_counter(),
                    {})
    over = [n for n, v, lim in out["checks"] if not harness.within(v, lim)]
    assert over == ["served_logit_gap"], out["checks"]
