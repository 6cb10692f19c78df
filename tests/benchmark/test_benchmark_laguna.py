"""The cell `laguna-s-2.1.agent-overload`: its configuration, its
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

from benchmark import flops_laguna as fl
from benchmark import harness
from benchmark.drivers import serve
from benchmark.references import laguna as ref

CELL = "laguna-s-2.1.agent-overload"
SEED = 2 ** 31 + 134
CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "laguna-s-2.1.json")))
# the catalog row Laguna-S-2.1 of the model-configs guide: every number of
# its `config`, typed in by hand
PUBLISHED = dict(
    vocab_size=100352, hidden_size=3072, intermediate_size=12288,
    num_hidden_layers=48, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=1048576, rms_norm_eps=1e-06,
    num_experts=256, num_experts_per_tok=10, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, decoder_sparse_step=1,
    sliding_window=512, moe_routed_scaling_factor=2.5,
    moe_router_logit_softcapping=0)
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
GROUPS = dict(
    model_type="laguna", attention_bias=False, norm_topk_prob=True,
    mlp_only_layers=[0], tie_word_embeddings=False, gating="per-head",
    moe_apply_router_weight_on_input=False,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=PERIOD * 12,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    gating_types=["per_head"] * 48,
    num_attention_heads_per_layer=[48, 72, 72, 72] * 12)
SMALL = dict(CONFIG, **CONFIG["rehearsal"])


# ------------------------------------------------------- the configuration
def test_the_file_holds_every_published_number_but_the_four_cuts():
    cuts = ["num_hidden_layers", "num_experts", "vocab_size",
            "max_position_embeddings"]
    assert CONFIG["reduced"] == cuts
    for key, value in PUBLISHED.items():
        if key in cuts:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    for key, value in GROUPS.items():
        assert CONFIG[key] == value, key
    assert [CONFIG[k] for k in cuts] == [5, 64, 25088, 16384]
    # the share: the router keeps the published 256 outputs and its top 10
    assert CONFIG["router_experts"] == PUBLISHED["num_experts"]
    kw = CONFIG["model_kwargs"]
    assert (kw["num_experts"], kw["held_experts"],
            kw["num_experts_per_tok"]) == (256, [0, 64], 10)
    assert 25088 * 4 == PUBLISHED["vocab_size"] and 64 * 4 == 256
    assert "4 chips share each layer" in CONFIG["cut"]["deployment"]
    assert "2 of the 5 layers are full" in CONFIG["cut"]["num_hidden_layers"]
    # no width differs from the source
    assert (kw["num_kv_heads"], kw["head_dim"], kw["sliding_window"],
            kw["moe_intermediate_size"],
            kw["shared_expert_intermediate_size"],
            kw["moe_routed_scaling_factor"]) == (8, 128, 512, 1024, 1024,
                                                 2.5)
    assert kw["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert kw["rope_parameters"] == GROUPS["rope_parameters"]
    assert kw["dtype"] == "bfloat16"
    for key in ("router", "gating", "qk_norm", "shared_expert",
                "rope_pairing", "initializer_range", "dtype",
                "routing_ties", "sampling", "matmul_precision",
                "host_share"):
        assert CONFIG["assumed"][key]


def test_the_model_builds_born_bfloat16_and_takes_the_references_names():
    model = harness.build_model(SMALL)
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    harness.load_weights(model, ref, SMALL, SEED)      # a miss is refused
    assert sorted(ref.to_program(ref.init_weights(SMALL, 128, SEED),
                                 SMALL)) == sorted(model.state_dict())
    blocks = model.model.layers
    assert [b.self_attn.heads for b in blocks] == [4, 6, 6, 6, 4]
    assert [b.self_attn.window for b in blocks] == [None, 8, 8, 8, None]
    assert blocks[1].mlp.w_gate.shape == [4, 128, 64]
    assert blocks[1].mlp.gate_weight.shape == [128, 16]
    assert blocks[0].mlp.gate_proj.weight.shape == [128, 256]


# -------------------------------------------------------------- the counts
FULL_ATTN = 2 * 3072 * 48 * 128 + 2 * 3072 * 1024 + 3072 * 48
WINDOW_ATTN = 2 * 3072 * 72 * 128 + 2 * 3072 * 1024 + 3072 * 72
EXPERT = 3 * 3072 * 1024
EVERY = 3072 * 256 + EXPERT
@pytest.mark.parametrize("got, want", [
    (fl.attn_params(CONFIG, 0), FULL_ATTN),
    (fl.attn_params(CONFIG, 1), WINDOW_ATTN),
    (fl.attn_params(CONFIG, 4), FULL_ATTN),
    (fl.kv_proj_params(CONFIG), 2 * 3072 * 1024),
    (fl.dense_params(CONFIG), 3 * 3072 * 12288),
    (fl.expert_params(CONFIG), EXPERT),
    (fl.every_token_expert_params(CONFIG), EVERY),
    (fl.head_params(CONFIG), 3072 * 25088),
    (fl.layers_of(CONFIG, fl.FULL), [0, 4]),
    (fl.layers_of(CONFIG, fl.WINDOW), [1, 2, 3]),
    ([fl.routed(CONFIG, i) for i in range(5)], [False] + [True] * 4),
    (fl.prefill_routed_layers(CONFIG), 3),
    (fl.pair_flops(CONFIG, fl.FULL), 2 * 4.0 * 48 * 128),
    (fl.pair_flops(CONFIG, fl.FULL, chunk=True), 4.0 * 48 * 128),
    (fl.pair_flops(CONFIG, fl.WINDOW), 3 * 4.0 * 72 * 128),
    (fl.pair_flops(CONFIG, fl.WINDOW, chunk=True), 3 * 4.0 * 72 * 128),
    (fl.visible_pairs(4, 10), 10 + 11 + 12 + 13 + 4),
    (fl.visible_pairs(4, 10, window=12), 11 + 12 + 12 + 12),
    (fl.visible_pairs(3, 600, window=512), 3 * 512),
    (fl.kv_bytes_per_token(CONFIG, fl.FULL), 2 * 2 * 8 * 128 * 2),
    (fl.kv_bytes_per_token(CONFIG, fl.WINDOW), 3 * 2 * 8 * 128 * 2),
    # a full layer's block: K and V of 16 positions in each of 2 layers
    (fl.paged_work(CONFIG, fl.FULL, 100, 32, 16),
     (2 * 4.0 * 48 * 128 * 1600,
      1600.0 * 8 * 128 * 2 * 2 * 2 + 2 * 2.0 * 32 * 48 * 128 * 2)),
    (fl.paged_work(CONFIG, fl.WINDOW, 33, 1, 16),
     (3 * 4.0 * 72 * 128 * 528,
      528.0 * 8 * 128 * 2 * 2 * 3 + 3 * 2.0 * 72 * 128 * 2)),
    (fl.held_expert_work(CONFIG, 100, 30),
     (2.0 * EXPERT * 100,
      30.0 * EXPERT * 2 + 100.0 * 3 * (3072 + 1024) * 2))])
def test_counts_against_hand_arithmetic(got, want):
    assert got == want


def test_the_cut_weighs_what_the_configuration_says():
    assert round(FULL_ATTN / 1e6, 2) == 44.19
    assert round(WINDOW_ATTN / 1e6, 2) == 63.14
    assert round(fl.held_params(CONFIG) / 1e6) == 3002
    assert round(fl.held_params(CONFIG) * 2 / 1e9, 2) == 6.0
    layer = EVERY + 64 * EXPERT
    assert round(4 * layer / 1e6, 1) == 2456.8
    # with 128 experts a layer (2 chips) the weights would be 10.8 GB: no
    # room for the reference's copy beside them
    two = fl.held_params(CONFIG) + 4 * 64 * EXPERT
    assert round(two * 2 / 1e9, 1) == 10.8
    # the whole model by the same arithmetic: 117.6B
    whole = 12 * FULL_ATTN + 36 * WINDOW_ATTN + 3 * 3072 * 12288 \
        + 47 * (3072 * 256 + 257 * EXPERT) + 2 * 3072 * 100352
    assert round(whole / 1e9, 1) == 117.6


def test_serve_flops_count_what_a_token_really_multiplies():
    decode = 2 * FULL_ATTN + 3 * WINDOW_ATTN + 3 * 3072 * 12288 \
        + 4 * EVERY + 3072 * 25088
    assert fl.decode_token_params(CONFIG) == decode
    # a prompt token: no head; of the last (full, routed) layer the K and
    # V projections alone
    chunk = decode - 3072 * 25088 - FULL_ATTN + 2 * 3072 * 1024 - EVERY
    assert fl.prefill_token_params(CONFIG) == chunk
    none = {fl.FULL: 0, fl.WINDOW: 0}
    assert fl.serve_flops(CONFIG, 512, 0, none, none, 0) == 2.0 * chunk * 512
    assert fl.serve_flops(CONFIG, 0, 32, none, none, 0) == 2.0 * decode * 32
    # attention by kind: a chunk's last layer (full) attends nothing
    assert fl.serve_flops(CONFIG, 0, 0, {fl.FULL: 1000, fl.WINDOW: 0},
                          none, 0) == 4.0 * 48 * 128 * 1000
    assert fl.serve_flops(CONFIG, 0, 0, none,
                          {fl.FULL: 1000, fl.WINDOW: 10}, 0) \
        == 2 * 4.0 * 48 * 128 * 1000 + 3 * 4.0 * 72 * 128 * 10
    assert fl.serve_flops(CONFIG, 0, 0, none, none, 7) == 2.0 * EXPERT * 7


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
    assert lp["router"].shape == (128, 16) and lp["e_up"].shape[0] == 4
    assert "bias" not in lp and "d_gate" in weights["layers"][0]
    assert [l["wq"].shape[1] // 32 for l in weights["layers"]] \
        == [4, 6, 6, 6, 4]
    assert lp["wg"].shape == (128, 6)
    kinds, ropes = weights.hyper[7], weights.hyper[8]
    assert kinds == tuple(PERIOD + PERIOD[:1])
    assert [r[1] for r in ropes] == [16, 32, 32]


def test_blocks_of_rows_change_no_number(weights, monkeypatch, capsys):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 64)))
    whole = np.asarray(ref.logits_fn(weights, ids))[0]
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    monkeypatch.setattr(ref, "ATTN_ROWS", 8)
    chosen = np.zeros(64, np.int32)
    chosen[10:50] = np.random.default_rng(1).integers(1, 512, 40)
    best, took, arg = ref.next_token_gaps(weights, ids, jnp.asarray(chosen),
                                          4, "float32")
    tied = np.asarray(ref.undecided.__wrapped__(weights, ids))
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


@pytest.mark.parametrize("control", ["fp8", "fp8:router", "fp8:experts",
                                     "bfloat16", "fp8+no_window"])
def test_a_control_is_another_computation(weights, control):
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 32)))
    exact = np.asarray(ref.logits_fn(weights, ids))
    other = np.asarray(ref.logits_fn(weights, ids, precision=control))
    whole = np.asarray(ref.logits_fn(weights, ids, precision="fp8"))
    assert 1e-4 < np.abs(exact - other).max() < 1.0
    # one part alone, or a finer significand, moves less than all in fp8
    assert "fp8" == control[:3] and ":" not in control \
        or np.abs(exact - other).mean() < np.abs(exact - whole).mean()
    for unknown in ("int4", "fp8:latent", "float32+no_rope"):
        with pytest.raises(ValueError):
            ref.logits_fn(weights, ids, precision=unknown)


# ------------------------------------------------------------- the readers
OFFSET = 1_790_000_000_123_456_789
MS = 1_000_000
# (step ms, rows, live full blocks, band blocks, held window blocks,
#  held experts touched, prefill (tokens, ctx))
STEPS = [(20.0, 30, 9000, 960, 1000, 120, (1024, 0)),
         (14.0, 31, 9100, 1000, 1040, 125, (256, 1024)),
         (30.0, 0, 0, 0, 0, None, (1024, 2048)),
         (12.2, 32, 9200, 1050, 1090, 128, None),
         (15.0, 32, 9300, 1056, 1094, 130, (64, 0)),
         (13.0, 32, 9300, 1056, 1096, 126, None)]
N_QUIET, N_TRACED = 3, 2


def synthetic(counted=True):
    recs, hs = [], []
    t = OFFSET - 400 * MS
    for i, (ms, rows, live, band, held, touched, chunk) in enumerate(STEPS):
        counts = {"decode_rows": rows, "kv_blocks_live": live,
                  "kv_blocks_walked": live + 3}
        if counted and touched is not None:
            # a row's 10 picks in 4 layers, of which 1 in 4 is held; a
            # chunk's in the 3 expert layers that run
            counts.update(
                window_blocks_live=held, window_blocks_walked=band,
                window_blocks_band=band, window_blocks_saved=live - held,
                window_blocks_freed=3,
                moe_assignments=rows * 10, moe_assignments_routed=rows * 40,
                experts_touched=touched,
                prefill_moe_assignments=chunk[0] * 7 if chunk else 0,
                prefill_moe_assignments_routed=chunk[0] * 30 if chunk
                else 0,
                prefill_experts_touched=190 if chunk else 0)
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


def test_mixed_serve_mfu_counts_attention_by_kind():
    read = harness.load_reader("mixed_serve_mfu.agent")
    # quiet steps 1..3
    prefilled, decoded = 256 + 1024, 31 + 32
    chunks = {fl.FULL: fl.visible_pairs(256, 1024)
              + fl.visible_pairs(1024, 2048),
              fl.WINDOW: (256 + 1024) * 512.0}
    pairs = {fl.FULL: (9100 - 15.5) * 16 + (9200 - 16) * 16,
             # a row sees at most its window
             fl.WINDOW: min(31 * 512, (1000 - 15.5) * 16)
             + min(32 * 512, (1050 - 16) * 16)}
    assert pairs[fl.WINDOW] == (1000 - 15.5) * 16 + 32 * 512
    local = 31 * 10 + 256 * 7 + 32 * 10
    want = fl.serve_flops(CONFIG, prefilled, decoded, chunks, pairs, local) \
        / 0.06 / 197e12
    assert read(synthetic()) == pytest.approx(100.0 * want)
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None       # the parent
    assert read(dict(synthetic(), quiet_s=0)) is None
    assert read({"program_spans": None}) is None


def test_window_blocks_held_share_is_held_over_one_full_table():
    read = harness.load_reader("window_blocks_held_share.agent")
    assert read(synthetic()) == pytest.approx(
        100.0 * (1040 + 1090) / (9100 + 9200))
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None


def test_the_two_paged_rooflines_read_two_kernels_by_their_names(capsys):
    full = harness.load_reader("full_paged_roofline.agent")
    window = harness.load_reader("window_paged_roofline.agent")
    run = dict(synthetic(), metric="x")
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:paged_decode_attention.2", 0.0, 7e6),
        ("mosaic:paged_decode_attention.3", 8e6, 7e6),
        ("mosaic:paged_window_decode_attention.1", 16e6, 2e6),
        ("mosaic:latent_paged_decode_attention.2", 30e6, 50e6)],
        "modules": []}}
    # the traced steps are the last two, 32 rows each
    flops_, bytes_ = fl.paged_work(CONFIG, fl.FULL, 9300 + 9300, 64, 16)
    assert bytes_ / 819e9 > flops_ / 197e12            # memory binds
    assert full(run) == pytest.approx(100.0 * bytes_ / 819e9 / 14e-3)
    flops_, bytes_ = fl.paged_work(CONFIG, fl.WINDOW, 1056 + 1056, 64, 16)
    assert window(run) == pytest.approx(100.0 * bytes_ / 819e9 / 2e-3)
    assert 0 < window(run) < 100 and 0 < full(run) < 100
    assert "memory binds" in capsys.readouterr().out
    run["trace"]["devices"][0]["ops"] = [
        ("mosaic:paged_decode_attention.2", 0.0, 9e6)]
    assert window(run) is None                    # nothing matched: nothing
    # another model's full layers have readers of their own
    other = dict(synthetic(counted=False), metric="x")
    other["trace"]["devices"] = run["trace"]["devices"]
    assert full(other) is None and window(other) is None


def test_held_expert_roofline_takes_its_shapes_from_this_configuration(
        capsys):
    read = harness.load_reader("held_expert_matmul_roofline.agent")
    assert read.__module__.endswith("held_expert_matmul_roofline_agent")
    run = dict(synthetic(), metric="held_expert_matmul_roofline.agent")
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:gmm.3", 0.0, 16e6), ("mosaic:gmm.11", 17e6, 14e6),
        ("mosaic:paged_decode_attention.3", 32e6, 5e6)], "modules": []}}
    assignments = 2 * 32 * 10 + 64 * 7
    flops_, bytes_ = fl.held_expert_work(CONFIG, assignments,
                                         130 + 126 + 190)
    least = max(flops_ / 197e12, bytes_ / 819e9)
    assert read(run) == pytest.approx(100.0 * least / 30e-3)
    assert 0 < read(run) < 100
    assert "memory binds" in capsys.readouterr().out
    run["trace"]["devices"][0]["ops"] = [("mosaic:ragged-dot-none.1", 0, 9e6)]
    assert read(run) is None
    assert read(dict(synthetic(counted=False), metric="x")) is None


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
    assert names[:5] == [
        "mixed_serve_mfu.agent", "window_paged_roofline.agent",
        "full_paged_roofline.agent", "held_expert_matmul_roofline.agent",
        "window_blocks_held_share.agent"]
    # another model's counts stay out
    for other in ("serve_mfu", "moe_serve_mfu", "hybrid_serve_mfu",
                  "paged_roofline", "gqa_paged_roofline",
                  "kda_step_roofline", "expert_matmul_roofline",
                  "experts_touched_share", "latent_paged_roofline",
                  "state_cache_share", "idle_explained_share"):
        assert other + ".agent" not in names
    assert len(names) == 16 and all(
        n.endswith(".agent") and callable(harness.load_reader(n))
        for n in names)
    mix = real["mix"]
    eng = mix["engine"]
    assert (eng["max_running"], eng["block_size"], eng["prefill_chunk"]) \
        == (32, 16, 1024)
    assert (mix["prompt_tokens"]["mean"], mix["prompt_tokens"]["sigma"],
            mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]) \
        == (6144, 0.9, 256, 12288)
    assert (mix["output_tokens"]["mean"], mix["output_tokens"]["sigma"],
            mix["output_tokens"]["min"], mix["output_tokens"]["max"]) \
        == (512, 0.7, 32, 2048)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        == 14336 <= real["config"]["max_position_embeddings"]
    assert not mix["follow_to_end"] and mix["check_requests"] == 16
    # the rate is 1.25 x the capacity the one sweep found
    knee = mix["knee"]
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        round(1.25 * knee["capacity_rate_per_s"] * 4) / 4)
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
    # a longer window and more requests checked than the sound run's: an
    # altered token shows only at a position the tie rule leaves compared
    # (about half of them at this size), and a loaded machine serves few
    spec = dict(spec, mix=dict(spec["mix"], check_requests=16))
    out = serve.run(spec, _args(build=_AlteredToken, seconds=4.0),
                    time.perf_counter(), {})
    over = [n for n, v, lim in out["checks"] if not harness.within(v, lim)]
    assert over == ["served_logit_gap"], out["checks"]
