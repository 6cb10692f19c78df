"""The cell `kimi-vl-a3b.doc-overload`: its reference, its counts, its
readers on synthetic runs, and a rehearsal of the run itself (tiny sizes,
the CPU): sound is `correct`, an altered token is not."""
import json
import os
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_moe_mla as fm
from benchmark import harness, program_spans
from benchmark.drivers import serve
from benchmark.references import kimi_vl as ref

CELL = "kimi-vl-a3b.doc-overload"
SEED = 2 ** 31 + 99
CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "kimi-vl-a3b.json")))
# the catalog row Kimi-VL-A3B-Instruct of the model-configs guide: every
# number of its `config`, typed in by hand
PUBLISHED = dict(
    vocab_size=163840, max_position_embeddings=131072, hidden_size=2048,
    intermediate_size=11264, moe_intermediate_size=1408,
    num_hidden_layers=27, num_attention_heads=16, n_shared_experts=2,
    n_routed_experts=64, ep_size=1, routed_scaling_factor=2.446,
    kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, n_group=1, topk_group=1, num_experts_per_tok=6,
    moe_layer_freq=1, first_k_dense_replace=1, num_key_value_heads=16,
    rms_norm_eps=1e-05, rope_theta=800000)
SMALL = dict(CONFIG, **CONFIG["rehearsal"])


# ------------------------------------------------------- the configuration
def test_the_file_holds_every_published_number_but_the_two_cuts():
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"],
            CONFIG["max_position_embeddings"]) == (5, 16384)
    # what the model is built from says the same as the top level
    for key, value in CONFIG["model_kwargs"].items():
        if key in CONFIG:
            assert CONFIG[key] == value, key
    assert CONFIG["model_kwargs"]["dtype"] == "bfloat16"


def test_the_model_builds_born_bfloat16_and_takes_the_references_names():
    model = harness.build_model(SMALL)
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    harness.load_weights(model, ref, SMALL, SEED)      # a miss is refused
    assert sorted(ref.to_program(ref.init_weights(SMALL, 128, SEED),
                                 SMALL)) == sorted(model.state_dict())


# -------------------------------------------------------------- the counts
@pytest.mark.parametrize("got, want", [
    # attention: W_q 2048 x 16 x 192, W_kva 2048 x 576, W_kvb 512 x 16 x
    # 256, W_o 2048 x 2048
    (fm.attention_params(CONFIG), 6291456 + 1179648 + 2097152 + 4194304),
    (fm.expert_params(CONFIG), 3 * 2048 * 1408),
    # an expert layer: attention + router + 64 experts + 2 shared = 584.8M
    (fm.layer_params(CONFIG, True),
     13762560 + 131072 + 64 * 8650752 + 2 * 8650752),
    # the dense layer: attention + 3 x 2048 x 11264 = 83.0M
    (fm.layer_params(CONFIG, False), 13762560 + 69206016),
    # a token multiplies 6 of the 64
    (fm.active_layer_params(CONFIG, True),
     13762560 + 131072 + 8 * 8650752),
    (fm.active_body_params(CONFIG),
     82968576 + 4 * (13762560 + 131072 + 8 * 8650752)),
    (fm.head_params(CONFIG), 2048 * 163840),
    (fm.latent_bytes_per_token(CONFIG), 1152),          # (512 + 64) x 2 B
    (fm.latent_flops_per_cached_token(CONFIG), 2 * 16 * (576 + 512)),
    (fm.attention_flops_per_pair(CONFIG), 2 * 16 * (192 + 128)),
    (fm.moe_layers(CONFIG), 4),
    (fm.visible_pairs(4, 10), 10 + 11 + 12 + 13 + 4),   # token i sees
    (fm.touched_expert_bytes(CONFIG, 10), 10 * 8650752 * 2)])
def test_counts_against_hand_arithmetic(got, want):
    assert got == want


def test_the_cut_weighs_what_the_configuration_says():
    held = fm.layer_params(CONFIG, False) + 4 * fm.layer_params(CONFIG, True) \
        + 2 * fm.head_params(CONFIG)            # embedding + untied head
    assert round(held / 1e6) == 3093 and round(held * 2 / 1e9, 2) == 6.19
    assert round(fm.layer_params(CONFIG, True) / 1e6, 1) == 584.8
    assert round(fm.layer_params(CONFIG, False) / 1e6, 1) == 83.0


def test_serve_flops_count_active_weights_the_head_once_and_the_pairs():
    body, head = fm.active_body_params(CONFIG), fm.head_params(CONFIG)
    # a prompt token: the dense layer, three expert layers, and of the
    # last layer only the 2048 x 576 projection of the row it caches
    chunk = fm.active_layer_params(CONFIG, False) \
        + 3 * fm.active_layer_params(CONFIG, True) + 2048 * 576
    assert fm.prefill_body_params(CONFIG) == chunk < body
    assert fm.serve_flops(CONFIG, 1024, 0, 0, 0) == 2.0 * chunk * 1024
    assert fm.serve_flops(CONFIG, 0, 24, 0, 0) == 2.0 * (body + head) * 24
    assert fm.serve_flops(CONFIG, 0, 0, 1000, 0) == 10240.0 * 4 * 1000
    assert fm.serve_flops(CONFIG, 0, 0, 0, 1000) == 10240.0 * 5 * 1000
    flops, nbytes = fm.latent_decode_work(CONFIG, 8000)
    assert (flops, nbytes) == (34816.0 * 8000 * 5, 1152 * 8000 * 5)


# ----------------------------------------------------------- the reference
@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)


def test_weights_follow_the_seed_and_the_selection_bias_is_not_zero(weights):
    again = ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)
    other = ref.init_weights(SMALL, 128, SEED + 1, dtype=jnp.float32)
    lp = weights["layers"][1]
    assert np.array_equal(lp["e_up"], again["layers"][1]["e_up"])
    assert not np.array_equal(lp["e_up"], other["layers"][1]["e_up"])
    assert float(jnp.abs(lp["bias"]).min()) > 0
    assert "router" not in weights["layers"][0] and "gate" in \
        weights["layers"][0]
    assert weights.hyper[:2] == (2, 2.446)


def test_blocks_of_rows_change_no_number(weights, monkeypatch):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 64)))
    whole = np.asarray(ref.logits_fn(weights, ids, 4))[0]
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    chosen = jnp.asarray(np.random.default_rng(1).integers(0, 512, 64))
    best, took, arg = ref.next_token_gaps.__wrapped__(
        weights, ids, chosen, 4, "float32")
    tied = np.asarray(ref.undecided.__wrapped__(weights, ids, 4))
    assert 0 < tied.sum() < 64
    np.testing.assert_allclose(best, whole.max(-1), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(took)[~tied],
        whole[np.arange(64), np.asarray(chosen)][~tied], atol=2e-6)
    np.testing.assert_array_equal(np.asarray(took)[tied],
                                  np.asarray(best)[tied])
    np.testing.assert_array_equal(arg, whole.argmax(-1))
    blocked = np.asarray(ref.logits_fn(weights, ids, 4))[0]
    np.testing.assert_allclose(blocked, whole, atol=2e-6)


@pytest.mark.parametrize("step, none_decided", [(2.0 ** -8, True),
                                                 (2.0 ** -6, False)])
def test_a_pick_within_two_bfloat16_steps_of_a_tie_carries_no_verdict(
        weights, step, none_decided):
    """A router of zeros scores every expert 0.5, so the bias alone picks
    and every position's margin is the bias's own step."""
    assert ref.ROUTE_TIE == 2.0 ** -7
    layers = [dict(lp, router=0 * lp["router"],
                   bias=-step * jnp.arange(8, dtype=jnp.float32))
              if "router" in lp else lp for lp in weights["layers"]]
    w = ref.Weights(dict(weights, layers=layers), weights.hyper)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 512, (1, 32)))
    chosen = jnp.zeros(32, jnp.int32)
    tied = np.asarray(ref.undecided(w, ids, 4))
    assert tied.all() if none_decided else not tied.any()
    best, took, _ = ref.next_token_gaps(w, ids, chosen, 4, "float32")
    gaps = np.asarray(best) - np.asarray(took)
    assert (gaps == 0).all() if none_decided else (gaps > 0).any()


@pytest.mark.parametrize("control", ["fp8", "fp8:router", "fp8:latent",
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
        ref.logits_fn(weights, ids, 4, "fp8:head")


# ------------------------------------------------------------- the readers
OFFSET = 1_790_000_000_123_456_789
MS = 1_000_000
# (step ms, rows, live blocks, experts touched, prefill (tokens, ctx))
STEPS = [(40.0, 20, 900, 200, (1024, 0)), (30.0, 24, 1000, 230, (512, 1024)),
         (35.0, 0, 0, None, (1024, 2048)), (32.0, 25, 1100, 240, None),
         (31.0, 25, 1100, 250, (64, 0)), (33.0, 25, 1100, 250, None)]
N_QUIET, N_TRACED = 3, 2


def synthetic(counted=True):
    recs, hs = [], []
    t = OFFSET - 400 * MS
    for i, (ms, rows, live, touched, chunk) in enumerate(STEPS):
        counts = {"decode_rows": rows, "kv_blocks_live": live,
                  "kv_blocks_walked": live + 3}
        if counted and touched is not None:
            # a chunk reaches 40 experts in each of the 3 expert layers
            # whose products run in a prefill program
            counts.update(moe_assignments=rows * 24,
                          experts_touched=touched,
                          prefill_moe_assignments=chunk[0] * 18
                          if chunk else 0,
                          prefill_experts_touched=120 if chunk else 0)
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if chunk:
            recs.append(("serving.prefill", t + MS, t + 9 * MS, 2000 + i,
                         1000 + i, 7,
                         dict(tokens=chunk[0], ctx=chunk[1]) if counted
                         else {}, "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [30.0] * N_QUIET,
            "quiet_s": 0.1, "chips": 1, "config": CONFIG,
            "mix": {"engine": {"block_size": 16}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"devices": {}, "spans": hs}}


def test_experts_touched_share_over_the_quiet_decode_steps():
    read = harness.load_reader("experts_touched_share.doc")
    # quiet steps 1..3: the step that decoded nothing has no count
    assert read(synthetic()) == pytest.approx(
        100.0 * (230 + 240) / (64 * 4 * 2))
    assert read(synthetic(counted=False)) is None       # the parent
    assert read({"program_spans": None}) is None


def test_moe_serve_mfu_from_the_spans_own_counts():
    read = harness.load_reader("moe_serve_mfu.doc")
    prefilled, decoded = 512 + 1024, 24 + 25
    chunks = fm.visible_pairs(512, 1024) + fm.visible_pairs(1024, 2048)
    rows = (1000 - 12) * 16 + (1100 - 12.5) * 16
    want = fm.serve_flops(CONFIG, prefilled, decoded, chunks, rows) \
        / 0.1 / 197e12
    assert read(synthetic()) == pytest.approx(100.0 * want)
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None
    assert read(dict(synthetic(), quiet_s=0)) is None


def test_latent_roofline_reads_the_kernel_by_its_name(capsys):
    read = harness.load_reader("latent_paged_roofline.doc")
    run = synthetic()
    run["metric"] = "latent_paged_roofline.doc"
    run["traced_context_sum"] = 500_000
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:latent_paged_decode_attention.3", 0.0, 4e6),
        ("mosaic:latent_paged_decode_attention.7", 5e6, 4e6),
        ("mosaic:paged_decode_attention.1", 9e6, 50e6),
        ("fusion.1", 60e6, 9e6)], "modules": []}}
    least = 1152 * 500_000 * 5 / 819e9            # memory binds
    assert read(run) == pytest.approx(100.0 * least / 8e-3)
    assert "memory binds" in capsys.readouterr().out
    run["trace"]["devices"][0]["ops"] = [("fusion.1", 0.0, 9e6)]
    assert read(run) is None                      # nothing matched: nothing
    assert read(dict(run, traced_context_sum=0)) is None


def test_expert_roofline_counts_assignments_and_touched_experts(capsys):
    from benchmark.metrics import expert_matmul_roofline as reader
    read = harness.load_reader("expert_matmul_roofline.doc")
    run = synthetic()
    run["metric"] = "expert_matmul_roofline.doc"
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:gmm.3", 0.0, 6e6), ("mosaic:gmm.11", 7e6, 4e6),
        ("mosaic:latent_paged_decode_attention.3", 12e6, 5e6)],
        "modules": []}}
    # the traced steps are the last two: 25 rows each, 250 experts
    # touched each, and one chunk of 64 tokens, which by the program's own
    # counts sent 64 x 6 x 3 assignments to 120 experts
    chunk_touched = 120
    assignments = 2 * 25 * 24 + 64 * 6 * 3
    flops_, bytes_ = reader.work(
        CONFIG, program_spans.steps_of(run["program_spans"])[-2:])
    assert flops_ == 2.0 * 8650752 * assignments
    assert bytes_ == pytest.approx(
        (500 + chunk_touched) * 8650752 * 2
        + assignments * 3 * (2048 + 1408) * 2)
    least = max(flops_ / 197e12, bytes_ / 819e9)
    assert read(run) == pytest.approx(100.0 * least / 10e-3)
    assert "memory binds" in capsys.readouterr().out
    # a program that counts no chunks leaves their weights out
    old = [(root[:6] + ({k: v for k, v in root[6].items()
                         if not k.startswith("prefill_")},) + root[7:],
            kids)
           for root, kids in program_spans.steps_of(run["program_spans"])]
    assert bytes_ - reader.work(CONFIG, old[-2:])[1] == pytest.approx(
        120 * 8650752 * 2 + 64 * 18 * 3 * (2048 + 1408) * 2)
    run["trace"]["devices"][0]["ops"] = [("mosaic:ragged-dot-none.1", 0, 9e6)]
    assert read(run) is None                 # XLA's own product: nothing
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
    assert [m["name"] for m in real["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    names = [m["name"] for m in real["per_layer"]]
    assert names[:4] == ["moe_serve_mfu.doc", "latent_paged_roofline.doc",
                         "experts_touched_share.doc",
                         "expert_matmul_roofline.doc"]
    assert "serve_mfu.doc" not in names     # counts a dense model
    assert all(n.endswith(".doc") and callable(harness.load_reader(n))
               for n in names)
    eng = real["mix"]["engine"]
    assert (eng["max_running"], eng["block_size"], eng["prefill_chunk"]) \
        == (32, 16, 1024)
    assert real["mix"]["prompt_tokens"]["max"] \
        + real["mix"]["output_tokens"]["max"] == 16384


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
