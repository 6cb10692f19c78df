"""The cell `keye-vl-2.0-30b-a3b.longctx-overload`: its configuration, its
reference and controls, its counts, its readers on synthetic runs, and a
rehearsal of the run itself (tiny sizes, the CPU)."""
import json
import os
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_keye as fk
from benchmark import harness, traffic
from benchmark.drivers import serve
from benchmark.references import keye_vl as ref

CELL = "keye-vl-2.0-30b-a3b.longctx-overload"
SEED = 2 ** 31 + 239
CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json")))
# the catalog row Keye-VL-2.0-30B-A3B of the model-configs guide: every
# number of its `config`, typed in by hand
PUBLISHED = dict(
    head_dim=128, hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48,
    moe_intermediate_size=768, num_attention_heads=32, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
    num_local_experts=128, rms_norm_eps=1e-06, rope_theta=10000000,
    vocab_size=151936, decoder_sparse_step=1)
GROUPS = dict(
    attention_bias=False, hidden_act="silu", mlp_only_layers=[],
    model_type="KeyeVL2", norm_topk_prob=True, sliding_window=None,
    tie_word_embeddings=False, use_sliding_window=False,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048})
SMALL = dict(CONFIG, **CONFIG["rehearsal"])
MIX = json.load(open(os.path.join(harness.HERE, "traffic",
                                  "longctx-overload.json")))
BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


# ------------------------------------------------------- the configuration
def test_the_file_holds_every_published_number_but_the_two_cuts():
    cuts = ["num_hidden_layers", "max_position_embeddings"]
    assert CONFIG["reduced"] == cuts
    for key, value in PUBLISHED.items():
        if key in cuts:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    for key, value in GROUPS.items():
        assert CONFIG[key] == value, key
    assert [CONFIG[k] for k in cuts] == [4, 51200]
    assert CONFIG["published"]["max_position_embeddings"] == 262144
    kw = CONFIG["model_kwargs"]
    assert (kw["num_kv_heads"], kw["head_dim"], kw["num_experts"],
            kw["num_experts_per_tok"], kw["moe_intermediate_size"],
            kw["index_n_heads"], kw["index_head_dim"], kw["index_topk"],
            kw["rope_theta"], kw["dtype"]) == (
        4, 128, 128, 8, 768, 16, 64, 2048, 10000000, "bfloat16")
    assert "12 pipeline stages of 4 whole layers" in CONFIG["cut"][
        "deployment"]
    for key in ("qk_norm", "mrope", "indexer_query", "indexer_key",
                "indexer_weights", "indexer_per_layer", "indexer_dtype",
                "chunks", "dtype", "init", "routing_tie_rule", "sampling"):
        assert CONFIG["assumed"][key]
    # the rehearsal selects: its top-k is a quarter or less of its prompts
    mix = MIX
    topk = CONFIG["rehearsal"]["model_kwargs"]["index_topk"]
    assert 4 * topk <= mix["rehearsal"]["prompt_tokens"]["min"]
    assert 4 * CONFIG["sa_config"]["topk"] <= mix["prompt_tokens"]["min"]


def test_the_model_builds_born_bfloat16_and_takes_the_references_names():
    model = harness.build_model(SMALL)
    assert {str(p._array.dtype) for p in model.parameters()} == {"bfloat16"}
    harness.load_weights(model, ref, SMALL, SEED)      # a miss is refused
    assert sorted(ref.to_program(ref.init_weights(SMALL, 128, SEED),
                                 SMALL)) == sorted(model.state_dict())
    assert model.cache_op == "sparse_paged_attention"
    assert [sorted(p) for p in model.cache_planes()] == [["ik", "k", "v"]] * 2


# -------------------------------------------------------------- the counts
ATTN = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
INDEXER = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16
ROUTER = 2048 * 128
EXPERT = 3 * 2048 * 768
HEAD = 2048 * 151936


@pytest.mark.parametrize("got, want", [
    (fk.attn_params(CONFIG), ATTN + INDEXER),
    (fk.kv_proj_params(CONFIG), 2 * 2048 * 512),
    (fk.index_key_params(CONFIG), 2048 * 64),
    (fk.router_params(CONFIG), ROUTER),
    (fk.expert_params(CONFIG), EXPERT),
    (fk.head_params(CONFIG), HEAD),
    (fk.scored_flops(CONFIG), 2.0 * 16 * 64),
    (fk.selected_flops(CONFIG), 4.0 * 32 * 128),
    (fk.indexer_work(CONFIG, 1000, 100, 10),
     (2.0 * 16 * 64 * 1000, 100.0 * 64 * 2 + 10.0 * 16 * (64 * 2 + 4))),
    (fk.expert_work(CONFIG, 96, 40),
     (2.0 * EXPERT * 96, (40.0 * EXPERT + 96 * 3 * (2048 + 768)) * 2)),
    (fk.sparse_prefill_work(CONFIG, 500, 300, 10),
     (4.0 * 32 * 128 * 500, 300.0 * 2048 + 10.0 * 2 * 32 * 128 * 2)),
    (fk.pool_bytes_per_token(CONFIG, 128), 4 * 2 * (2 * 4 * 128 + 128))])
def test_counts_against_hand_arithmetic(got, want):
    assert got == want


def test_the_cut_weighs_what_the_configuration_says():
    """3,124M parameters, 6.25 GB in bfloat16: layers 0-3 whole (625.4M
    each: attention 18.87M, indexer 2.26M, router 0.26M, 128 experts of
    4.72M), the embedding and the head (622.3M)."""
    assert round(ATTN / 1e6, 2) == 18.87
    assert round(INDEXER / 1e6, 2) == 2.26
    assert round(128 * EXPERT / 1e6, 2) == 603.98
    norms = 2 * 2048 + 2 * 128 + 2 * 64
    assert round((ATTN + INDEXER + ROUTER + 128 * EXPERT + norms) / 1e6,
                 1) == 625.4
    assert round(2 * HEAD / 1e6, 1) == 622.3
    assert fk.held_params(CONFIG) == ref.parameter_count(CONFIG)
    assert round(fk.held_params(CONFIG) / 1e6) == 3124
    assert round(fk.held_params(CONFIG) * 2 / 1e9, 2) == 6.25
    # what the built model holds, at the rehearsal's size
    model = harness.build_model(SMALL)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == fk.held_params(SMALL) == ref.parameter_count(SMALL)


def test_serve_flops_count_what_a_token_really_multiplies():
    decode = 4 * (ATTN + INDEXER + ROUTER) + HEAD
    assert fk.decode_token_params(CONFIG) == decode
    # a prompt token: no head; of the last layer the K, V and indexer key
    # projections alone
    chunk = decode - HEAD - (ATTN + INDEXER + ROUTER) + 2 * 2048 * 512 \
        + 2048 * 64
    assert fk.prefill_token_params(CONFIG) == chunk
    assert fk.serve_flops(CONFIG, 512, 0, 0, 0, 0, 0, 0) == 2.0 * chunk * 512
    assert fk.serve_flops(CONFIG, 0, 12, 0, 0, 0, 0, 0) == 2.0 * decode * 12
    assert fk.serve_flops(CONFIG, 0, 0, 7, 0, 0, 0, 0) == 2.0 * EXPERT * 7
    # a decode step's pairs in all four layers, a chunk's in three
    assert fk.serve_flops(CONFIG, 0, 0, 0, 100, 10, 0, 0) \
        == 4 * (2.0 * 16 * 64 * 100 + 4.0 * 32 * 128 * 10)
    assert fk.serve_flops(CONFIG, 0, 0, 0, 0, 0, 100, 10) \
        == 3 * (2.0 * 16 * 64 * 100 + 4.0 * 32 * 128 * 10)


def test_the_pool_holds_the_twelve_longest_requests_of_a_whole_run():
    """num_blocks: the 12 longest requests of a whole run (lead-in and
    window) + 10%, counted by the generator itself; and what that pool
    takes beside the weights."""
    spec = harness.load_cell(CELL)
    mix = spec["mix"]
    schedule = traffic.serve_schedule(mix, SEED, spec["run_seconds"], 100)
    blocks = sorted((-(-(len(r["prompt"]) + r["max_new_tokens"]) // 16)
                     for r in schedule), reverse=True)[:12]
    assert mix["engine"]["num_blocks"] == round(1.1 * sum(blocks), -2)
    pool = mix["engine"]["num_blocks"] * 16 * fk.pool_bytes_per_token(
        CONFIG, 128)
    # 41,100 blocks at the mix's 2.5 req/s: 6.06 GB
    assert 5.5e9 < pool < 6.5e9
    assert (pool + fk.held_params(CONFIG) * 2) / 16e9 > 0.25


# ----------------------------------------------------------- the reference
@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)


def test_weights_follow_the_seed(weights):
    again = ref.init_weights(SMALL, 128, SEED, dtype=jnp.float32)
    other = ref.init_weights(SMALL, 128, SEED + 1, dtype=jnp.float32)
    lp = weights["layers"][1]
    assert np.array_equal(lp["i_wq"], again["layers"][1]["i_wq"])
    assert not np.array_equal(lp["i_wq"], other["layers"][1]["i_wq"])
    assert lp["router"].shape == (128, 8) and lp["e_up"].shape == (8, 128,
                                                                   64)
    assert not np.asarray(lp["i_kn_b"]).any()
    assert weights.hyper[-1] == 16


def test_blocks_of_rows_change_no_number(weights, monkeypatch, capsys):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 128)))
    whole = np.asarray(ref.logits_fn(weights, ids))[0]
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)
    monkeypatch.setattr(ref, "ATTN_ROWS", 16)
    monkeypatch.setattr(ref, "KEY_BLOCK", 32)
    chosen = np.zeros(128, np.int32)
    chosen[70:120] = np.random.default_rng(1).integers(1, 512, 50)
    best, took, arg = ref.next_token_gaps(weights, ids, jnp.asarray(chosen),
                                          4, "float32")
    tied = np.asarray(ref.undecided(weights, ids))
    np.testing.assert_allclose(best, whole.max(-1), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(took)[~tied], whole[np.arange(128), chosen][~tied],
        atol=2e-5)
    np.testing.assert_array_equal(np.asarray(took)[tied],
                                  np.asarray(best)[tied])
    np.testing.assert_array_equal(arg, whole.argmax(-1))
    # the share left uncompared is said aloud, over the served positions
    assert (f"50 served positions, {tied[70:120].sum()} within 2 bfloat16"
            in capsys.readouterr().err)


@pytest.mark.parametrize("control", ["fp8", "bfloat16", "float32+dense",
                                     "float32+recent", "fp8+dense"])
def test_a_control_is_another_computation(weights, control):
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 128)))
    exact = np.asarray(ref.logits_fn(weights, ids))
    other = np.asarray(ref.logits_fn(weights, ids, precision=control))
    assert np.isfinite(other).all() and np.abs(exact - other).max() > 1e-4
    if "+" in control:
        # a fault in the selection leaves the positions that see no more
        # than the top-k as they were (in float32)
        same = np.abs(exact - other)[0, :16].max()
        assert same < 1e-5 or control.startswith("fp8")
    for unknown in ("int4", "float32+sparse", "fp8:router"):
        with pytest.raises(ValueError):
            ref.logits_fn(weights, ids, precision=unknown)


# ------------------------------------------------------------- the readers
OFFSET = 1_790_000_000_123_456_789
MS = 1_000_000
# (step ms, rows, indexer positions, selected positions, prefill (tokens,
#  ctx))
STEPS = [(90.0, 10, 240000, 20480, (2048, 0)),
         (95.0, 11, 260000, 22528, (2048, 2048)),
         (120.0, 0, 0, 0, (2048, 20000)),
         (20.0, 12, 300000, 24576, None),
         (96.0, 12, 300012, 24576, (2048, 4096)),
         (21.0, 12, 300024, 24576, None)]
N_QUIET, N_TRACED = 3, 2


def _chunk(n, ctx, topk=2048):
    seen = range(ctx + 1, ctx + n + 1)
    live = -(-(ctx + n) // 16)
    return dict(tokens=n, ctx=ctx, kv_blocks_live=live,
                kv_blocks_walked=live + 8, scored_pairs=sum(seen),
                selected_pairs=sum(min(m, topk) for m in seen),
                attended_pairs=sum(seen))


def synthetic(counted=True):
    recs, hs = [], []
    t = OFFSET - 800 * MS
    for i, (ms, rows, scored, selected, chunk) in enumerate(STEPS):
        counts = {"decode_rows": rows, "kv_blocks_live": scored // 16,
                  "kv_blocks_walked": scored // 16 + rows}
        if counted:
            counts.update(rows_picked_on_device=rows,
                          rows_chained=max(rows - 1, 0),
                          indexer_positions=scored,
                          selected_positions=selected,
                          moe_assignments=rows * 32,
                          prefill_moe_assignments=chunk[0] * 24 if chunk
                          else 0)
        end = t + round(ms * MS)
        recs.append(("serving.step", t, end, 1000 + i, None, None, counts,
                     "serving", 1))
        if chunk:
            kid = _chunk(*chunk) if counted else dict(tokens=chunk[0],
                                                      ctx=chunk[1])
            recs.append(("serving.prefill", t + MS, t + 60 * MS, 2000 + i,
                         1000 + i, 7, kid, "serving", 1))
        if i >= len(STEPS) - N_TRACED:
            hs.append(("engine.step", float(t - 3_000 - OFFSET),
                       float(end - t + 7_000)))
        t = end + 200_000
    return {"program_spans": recs, "step_ms": [90.0] * N_QUIET,
            "quiet_s": 0.3, "chips": 1, "config": CONFIG,
            "mix": {"engine": {"block_size": 16}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"devices": {}, "spans": hs}, "metric": "x"}


def test_selected_kv_share_is_selected_over_scored():
    read = harness.load_reader("selected_kv_share.longctx")
    # quiet steps 1..3
    assert read(synthetic()) == pytest.approx(
        100.0 * (22528 + 0 + 24576) / (260000 + 0 + 300000))
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None       # the parent
    assert read({"program_spans": None}) is None


def test_sparse_serve_mfu_counts_the_models_pairs():
    read = harness.load_reader("sparse_serve_mfu.longctx")
    chunks = [_chunk(2048, 2048), _chunk(2048, 20000)]
    want = fk.serve_flops(
        CONFIG, 4096, 11 + 12, 11 * 32 + 12 * 32 + 4096 * 24,
        260000 + 300000, 22528 + 24576,
        sum(c["scored_pairs"] for c in chunks),
        sum(c["selected_pairs"] for c in chunks)) / 0.3 / 197e12
    assert read(synthetic()) == pytest.approx(100.0 * want)
    assert 0 < read(synthetic()) < 100
    assert read(synthetic(counted=False)) is None
    assert read(dict(synthetic(), quiet_s=0)) is None


QUIET = STEPS[1:1 + N_QUIET]


@pytest.mark.parametrize("name, want", [
    ("paged_live_block_share",
     100.0 * sum(sc // 16 for _, _, sc, _, _ in QUIET)
     / sum(sc // 16 + r for _, r, sc, _, _ in QUIET)),
    ("prefill_live_block_share",
     100.0 * sum(_chunk(*c)["kv_blocks_live"] for *_, c in QUIET if c)
     / sum(_chunk(*c)["kv_blocks_walked"] for *_, c in QUIET if c)),
    ("device_pick_share", 100.0),
    ("chained_row_share",
     100.0 * sum(max(r - 1, 0) for _, r, *_ in QUIET)
     / sum(r for _, r, *_ in QUIET))])
def test_the_engines_own_readers_read_this_cell(name, want):
    """The readers of counts `LLMEngine` writes for every model read this
    cell unchanged: the indexer's walk stands for a decode step's blocks
    (`ops.pallas.sparse_blocks_read`), a chunk's walk for its own."""
    read = harness.load_reader(name + ".longctx")
    assert read(synthetic()) == pytest.approx(want)
    assert 0 < want <= 100
    assert read({"program_spans": None}) is None


OPS = [("mosaic:indexer_decode_scores.1", 0.0, 2e6),
       ("mosaic:indexer_prefill_scores.2", 3e6, 9e6),
       ("fusion.3", 13e6, 4e6),
       ("mosaic:sparse_prefill_attention.4", 18e6, 30e6),
       ("mosaic:paged_decode_attention.5", 50e6, 50e6),
       ("mosaic:gmm.6", 101e6, 40e6)]


def test_the_three_rooflines_read_their_kernels_by_name(capsys):
    run = synthetic()
    run["trace"]["devices"] = {0: {"ops": OPS, "modules": []}}
    # the traced steps are the last two: 24 rows, one chunk at 4,096
    chunk = _chunk(2048, 4096)
    rows, scored, selected = 24, 600036, 49152
    idx = harness.load_reader("indexer_roofline.longctx")
    f1, b1 = fk.indexer_work(CONFIG, 4 * scored, 4 * scored, 4 * rows)
    f2, b2 = fk.indexer_work(CONFIG, 3 * chunk["scored_pairs"],
                             3 * (4096 + 2048), 3 * 2048)
    least = max(f1 / 197e12, b1 / 819e9) + max(f2 / 197e12, b2 / 819e9)
    assert idx(run) == pytest.approx(100.0 * least / 11e-3)
    gmm = harness.load_reader("expert_matmul_roofline.longctx")
    f, b = fk.expert_work(CONFIG, 24 * 32 + 2048 * 24, 0)
    assert gmm(run) == pytest.approx(
        100.0 * max(f / 197e12, b / 819e9) / 40e-3)
    pre = harness.load_reader("sparse_prefill_roofline.longctx")
    f, b = fk.sparse_prefill_work(CONFIG, 3 * chunk["selected_pairs"],
                                  3 * (4096 + 2048), 3 * 2048)
    assert pre(run) == pytest.approx(
        100.0 * max(f / 197e12, b / 819e9) / 30e-3)
    for read in (idx, gmm, pre):
        assert 0 < read(run) < 100
    assert "memory binds" in capsys.readouterr().out
    # nothing matched, or the parent's spans: nothing, never 0
    run["trace"]["devices"][0]["ops"] = OPS[2:3] + OPS[4:5]
    assert idx(run) is None and gmm(run) is None and pre(run) is None
    other = synthetic(counted=False)
    other["trace"]["devices"] = {0: {"ops": OPS, "modules": []}}
    assert idx(other) is None and gmm(other) is None and pre(other) is None


def test_a_roofline_over_100_percent_is_refused(capsys):
    run = synthetic()
    run["trace"]["devices"] = {0: {"ops": [
        ("mosaic:sparse_prefill_attention.4", 0.0, 1e3)], "modules": []}}
    assert harness.load_reader("sparse_prefill_roofline.longctx")(run) is None
    assert "REFUSED" in capsys.readouterr().out


# ------------------------------------------------------------------ the run
def _args(build=None, seconds=2.0):
    return types.SimpleNamespace(seed=SEED, seconds=seconds, trace=0,
                                 rehearse=True, build=build)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, rehearse=True)


def test_the_cell_reports_its_metrics_by_name():
    real = harness.load_cell(CELL)
    assert real["cell"]["chips"] == 1
    assert [m["name"] for m in real["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    names = [m["name"] for m in real["per_layer"]]
    for name in ("sparse_serve_mfu", "indexer_roofline",
                 "sparse_prefill_roofline", "expert_matmul_roofline",
                 "selected_kv_share", "decode_batch_mean",
                 "engine_step_ms_p50", "device_idle_share",
                 "step_schedule_ms_p50", "step_dispatch_ms_p50",
                 "step_fetch_ms_p50", "step_sample_ms_p50",
                 "setup_trace_s", "setup_compile_s",
                 "paged_live_block_share", "prefill_live_block_share",
                 "device_pick_share", "chained_row_share"):
        assert name + ".longctx" in names
    # another model's counts stay out, and no reader of nothing
    for other in ("serve_mfu", "moe_serve_mfu", "mixed_serve_mfu",
                  "paged_roofline", "full_paged_roofline",
                  "latent_paged_roofline", "idle_explained_share",
                  "sparse_decode_roofline"):
        assert other + ".longctx" not in names
    assert all(n.endswith(".longctx") and callable(harness.load_reader(n))
               for n in names)
    mix = real["mix"]
    eng = mix["engine"]
    assert (eng["max_running"], eng["block_size"], eng["prefill_chunk"]) \
        == (12, 16, 2048)
    assert (mix["prompt_tokens"]["mean"], mix["prompt_tokens"]["sigma"],
            mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]) \
        == (24576, 0.6, 8192, 49152)
    assert (mix["output_tokens"]["mean"], mix["output_tokens"]["sigma"],
            mix["output_tokens"]["min"], mix["output_tokens"]["max"]) \
        == (512, 0.7, 64, 2048)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        == 51200 == real["config"]["max_position_embeddings"]
    assert not mix["follow_to_end"] and mix["check_requests"] == 6
    assert (mix["lead_s"], mix["trace_seconds"]) == (20.0, 3.0)
    assert len(real["cell"]["why"]) <= 200


def test_the_cells_entries_close_their_lists():
    """The configuration, the cell and its per-layer entries are the last
    of their lists, in one run each, and every per-layer entry names the
    cell alone; the end-to-end metric the cell reports names it last."""
    assert BENCH["configs"][-1]["name"] == CONFIG["name"] == \
        "keye-vl-2.0-30b-a3b"
    assert BENCH["configs"][-1]["file"] == \
        "benchmark/configs/keye-vl-2.0-30b-a3b.json"
    assert BENCH["configs"][-1]["reduced"] == CONFIG["reduced"]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["workloads"][-1]["traffic"] == "longctx-overload"
    ours = [i for i, m in enumerate(BENCH["per_layer"])
            if m["name"].endswith(".longctx")]
    assert ours == list(range(len(BENCH["per_layer"]) - len(ours),
                              len(BENCH["per_layer"])))
    assert all(BENCH["per_layer"][i]["workloads"] == [CELL] for i in ours)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL
    assert all(CELL not in m.get("workloads", [])
               for name, m in e2e.items() if name != "serve_tokens_per_s")
    # the mix's one limit is the one the harness compares
    assert list(MIX["limits"]) == ["served_logit_gap"]


def test_sound_rehearsal_run_is_correct(spec):
    out = serve.run(spec, _args(), time.perf_counter(), {})
    over = [n for n, v, lim in out["checks"] if not harness.within(v, lim)]
    assert not over, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["e2e"]["serve_tokens_per_s"] > 0
