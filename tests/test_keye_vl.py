"""`text/keye_vl.py` against its plain reference
(`benchmark/references/keye_vl.py`) on seeded weights, small size, CPU:
one forward, prefill chunks and decode steps through the serving pool at
contexts of 4-8 x the indexer's top-k, the controls that must fail the
same comparison, the indexer's key plane, and the XLA and kernel forms
of `sparse_paged_attention`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import harness
from benchmark.references import keye_vl as ref
from paddle_tpu.ops import nn_kernels
from paddle_tpu.ops import pallas as pallas_ops
from paddle_tpu.serving import LLMEngine

CONFIG = json.load(open(os.path.join(
    harness.ROOT, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json")))
# the file's own small size (hidden 128, 4 heads over 2 of 128, 16
# indexer heads of 64, top 16 of the positions, 8 experts, top 2), in
# float32 and wider weights so that a wrong model shows
SMALL = dict(CONFIG, **CONFIG["rehearsal"], initializer_range=0.2)
SMALL["model_kwargs"] = dict(SMALL["model_kwargs"], dtype="float32",
                             initializer_range=0.2)
TOPK = SMALL["sa_config"]["topk"]
SEED, LENGTH = 2 ** 31 + 39, 128      # 8 x the top-k
TOL = 2e-4                            # float32 on both sides


def _model(weights, cfg=SMALL):
    pt.seed(0)
    m = harness.build_model(cfg)
    missing, unexpected = m.set_state_dict(ref.to_program(weights, cfg))
    assert not missing and not unexpected
    m.eval()
    return m


@pytest.fixture(scope="module")
def weights():
    with jax.default_matmul_precision("highest"):
        return ref.init_weights(SMALL, LENGTH, SEED, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    return _model(weights)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, 512, (1, LENGTH))


def _ref(weights, ids, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_fn(weights, jnp.asarray(ids),
                                        precision=precision))


@pytest.fixture(scope="module")
def want(weights, ids):
    return _ref(weights, ids)


def _logits(model, ids, caches=None):
    return np.asarray(model(pt.to_tensor(np.asarray(ids, "int64")),
                            caches=caches)._array)


def test_the_layer_holds_what_the_configuration_says(model):
    attn = model.model.layers[0].self_attn
    assert attn.q_proj.weight.shape == [128, 4 * 128]
    assert attn.indexer.wq.weight.shape == [128, 16 * 64]
    assert attn.indexer.wk.weight.shape == [128, 64]
    assert attn.indexer.weights_proj.weight.shape == [128, 16]
    assert model.model.layers[0].mlp.w_gate.shape == [8, 128, 64]
    assert model.cache_op == "sparse_paged_attention"
    assert model.cache_op_args == {"topk": TOPK, "index_heads": 16}
    assert [dict(p) for p in model.cache_planes()] == [
        {"k": (2, 128), "v": (2, 128), "ik": (128,)}] * 2


def test_one_forward_is_the_reference(model, ids, want):
    np.testing.assert_allclose(_logits(model, ids), want, atol=TOL, rtol=0)


def test_the_growing_and_preallocated_caches_are_the_reference(model, ids,
                                                               want):
    from paddle_tpu import tensor_api as T
    steps = [(0, 80)] + [(i, i + 1) for i in range(80, 96)]
    growing, prealloc = model.new_caches(1), model.new_caches(
        1, max_length=LENGTH)
    for caches in (growing, prealloc):
        got = []
        for start, stop in steps:
            if caches is prealloc:
                for c in caches:
                    c["pos"] = T.full([], start, dtype="int32")
            got.append(_logits(model, ids[:, start:stop], caches))
        np.testing.assert_allclose(np.concatenate(got, 1), want[:, :96],
                                   atol=TOL, rtol=0)


def _served(model, prompts, new):
    """Greedy tokens of an engine over the pool (chunks of 32) and the
    float32 logits of every decode step, by request."""
    eng = LLMEngine(model, num_blocks=96, block_size=16, max_running=4,
                    prefill_chunk=32)
    rows = {}
    emit = eng._emit

    def keep(req, row, now):
        rows.setdefault(req.id, []).append(np.asarray(row))
        return emit(req, row, now)

    eng._emit = keep
    reqs = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    eng.run()
    assert eng.pool.check_leaks() == ([], [])
    return eng, [(list(r.generated), np.stack(rows[r.id])) for r in reqs]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(0, 512, n) for n in (64, 100, 77)]   # 4-6 x top-k


@pytest.fixture(scope="module")
def served(model, prompts):
    return _served(model, prompts, 12)[1]


def test_prefill_then_decode_through_the_pool_is_the_reference(
        weights, prompts, served):
    """Every decode step's logits, after chunked prefill, against the
    reference's full forward over the prompt and the served tokens."""
    for prompt, (tokens, rows) in zip(prompts, served):
        feed = np.concatenate([prompt, tokens[:-1]])
        pad = -len(feed) % 128
        full = _ref(weights, np.pad(feed, (0, pad))[None])[0]
        np.testing.assert_allclose(rows, full[len(prompt) - 1:len(feed)],
                                   atol=TOL, rtol=0)


def test_the_selection_controls_disagree_past_the_top_k(weights, ids, want):
    """With the selection left out (every s <= t attended) or replaced by
    the last top-k positions the reference is another model: past the
    top-k its logits leave the program's far behind the tolerance."""
    for control in ("float32+dense", "float32+recent"):
        gap = np.abs(_ref(weights, ids, control) - want)
        assert gap[:, :TOPK].max() < TOL, control  # nothing to leave out
        assert gap[:, 4 * TOPK:].max() > 100 * TOL, control


def test_a_top_k_over_the_context_is_plain_causal_gqa(weights, ids):
    wide = dict(SMALL, model_kwargs=dict(SMALL["model_kwargs"],
                                         index_topk=LENGTH))
    np.testing.assert_allclose(_logits(_model(weights, wide), ids),
                               _ref(weights, ids, "float32+dense"),
                               atol=TOL, rtol=0)


def test_the_ik_plane_holds_the_reference_keys(weights, model, prompts):
    prompt = prompts[0]
    with jax.default_matmul_precision("highest"):
        keys = np.asarray(ref.index_keys(weights, jnp.asarray(prompt)))
    eng = LLMEngine(model, num_blocks=96, block_size=16, max_running=4,
                    prefill_chunk=32)
    req = eng.add_request(prompt, max_new_tokens=4)
    eng.step()
    eng.step()
    table = req.block_tables[0]
    n = req.ctx
    for layer, plane in enumerate(eng.pool.planes["ik"]):
        got = np.asarray(plane)[np.asarray(table)].reshape(-1, 128)[:n]
        np.testing.assert_allclose(got[:, :64], keys[layer, :n], atol=1e-4)
        assert not got[:, 64:].any()        # the lanes past the key: zeros


def _op_inputs(rng, s, pos, heads=4, kv=2, width=128, n_idx=16):
    N, bs, M = 40, 16, 10
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tables = jnp.asarray(rng.permutation(N)[:2 * M].reshape(2, M), jnp.int32)
    return (f(2, s, heads, 128), f(N, bs, kv, 128), f(N, bs, kv, 128),
            f(N, bs, width), f(2, s, n_idx, width), f(2, s, n_idx), tables,
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("s,pos", [(1, [70, 9]), (1, [150, 0]),
                                   (32, [40, 0]), (32, [120, 3])],
                         ids=["decode", "decode-full-table", "chunk",
                              "chunk-late"])
def test_the_kernel_forms_are_the_xla_form(monkeypatch, s, pos):
    """`sparse_paged_attention` on the indexer and attention kernels
    (interpret mode) against its XLA form: decode rows that see more and
    fewer positions than the top-k, and prefill chunks."""
    args = _op_inputs(np.random.default_rng(s + pos[0]), s, pos)
    want = nn_kernels.sparse_paged_attention_k(*args, topk=16)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    got = pallas_ops.sparse_paged_attention_with_pallas(*args, topk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_indexer_kernel_scores_what_the_xla_form_scores(monkeypatch):
    from paddle_tpu.ops.pallas import sparse_attention as sa
    q, _, _, ik, qi, wi, tables, pos = _op_inputs(
        np.random.default_rng(3), 32, [40, 0])
    got = np.asarray(sa.indexer_scores(qi, wi, ik, tables, pos,
                                       interpret=True))
    keys = nn_kernels.paged_gather_k(ik, tables)
    want = np.asarray(jnp.where(
        nn_kernels.paged_visible(32, keys.shape[1], pos),
        nn_kernels.indexer_scores(qi, wi, keys), -jnp.inf))
    assert got.shape == (2, 32, sa.scored_len(10, 16))
    np.testing.assert_allclose(got[..., :160], want, atol=1e-4)
    assert np.isneginf(got[..., 160:]).all()


def _run_tables(n_blocks, cols=70):
    """Two tables over a pool of `n_blocks` blocks whose ids run in every
    way the indexer's walk meets (chunks of 32 blocks): row 0 one
    ascending run that ends at the pool's last block; row 1 a descending
    stretch, then runs of every power-of-two length up to a chunk and one
    of 2, which cross the chunk boundaries at 32 and 64."""
    row = list(range(40, 35, -1))
    start = 50
    for length in (1, 2, 4, 8, 16, 32, 2):
        row += list(range(start, start + length))
        start += length + 3
    assert len(row) == cols
    return np.asarray([np.arange(n_blocks - cols, n_blocks), row], np.int32)


@pytest.mark.parametrize("s", [1, 16, 32], ids=["decode", "chunk16",
                                                "chunk32"])
def test_the_indexer_walk_copies_runs_as_it_copies_blocks(s):
    """The indexer kernel (interpret mode) over tables of runs gives, to
    the bit, what it gives over the same keys laid out so that no two
    neighbours of a table lie side by side in the pool (one copy a
    block), and the XLA form's scores; both rows end in a partial
    chunk."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    N, bs, h, M = 160, 16, 4, 70
    rng = np.random.default_rng(s)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ik, qi, wi = f(N, bs, 128), f(2, s, h, 128), f(2, s, h)
    tables = _run_tables(N, M)
    pos = np.asarray([M * bs - s, 700], np.int32)
    # block x of the pool lies at 7x mod N in the other: a run of ids is
    # no run there (the tables hold no jump of 23, 7's inverse mod 160)
    at = 7 * np.arange(N) % N
    apart = at[tables]
    assert (np.diff(apart, axis=1) != 1).all()
    assert sa.walk_copies(apart, pos, bs, s, h) > 2 * sa.walk_copies(
        tables, pos, bs, s, h)
    got = np.asarray(sa.indexer_scores(qi, wi, ik, jnp.asarray(tables),
                                       jnp.asarray(pos), interpret=True))
    moved = jnp.zeros_like(ik).at[at].set(ik)
    per_block = np.asarray(sa.indexer_scores(
        qi, wi, moved, jnp.asarray(apart), jnp.asarray(pos),
        interpret=True))
    np.testing.assert_array_equal(got, per_block)
    keys = nn_kernels.paged_gather_k(ik, jnp.asarray(tables))
    want = np.asarray(jnp.where(
        nn_kernels.paged_visible(s, keys.shape[1], jnp.asarray(pos)),
        nn_kernels.indexer_scores(qi, wi, keys), -jnp.inf))
    np.testing.assert_allclose(got[..., :M * bs], want, atol=1e-4)
    assert np.isneginf(got[..., M * bs:]).all()


def _copies_one_by_one(tables, pos, bs, queries, heads):
    """The walk's DMAs counted a program, a chunk and a run at a time."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    chunk = sa.index_chunk(bs)
    tq = 1 if queries == 1 else sa.index_tile(queries, heads)
    total = 0
    for row, p in zip(tables, pos):
        for t in range(queries // tq):
            n = min(-(-(p + t * tq + tq) // bs), len(row))
            for a in range(0, n, chunk):
                blocks = list(row[a:min(a + chunk, n)])
                i = 0
                while i < len(blocks):
                    j = i
                    while j + 1 < len(blocks) and \
                            blocks[j + 1] == blocks[j] + 1:
                        j += 1
                    total += bin(j - i + 1).count("1")
                    i = j + 1
    return total


@pytest.mark.parametrize("seed", range(4))
def test_the_hosts_copy_count_is_the_walks_rule(seed):
    """`walk_copies` against the walk's DMAs counted one by one, over
    random tables of runs, descending stretches and repeated ids, decode
    rows and chunks of one and of several programs."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    rng = np.random.default_rng(seed)
    for _ in range(25):
        B, M = int(rng.integers(1, 5)), int(rng.choice([1, 37, 70, 139]))
        rows = []
        for _ in range(B):
            row = []
            while len(row) < M:
                length = int(rng.integers(1, 45))
                start = int(rng.integers(length, 500))
                step = 1 if rng.random() < 0.7 else -1
                row += [start + step * i for i in range(length)]
            rows.append(row[:M])
        tables = np.asarray(rows, np.int32)
        tables[:, int(rng.integers(0, M)):] *= rng.random() < 0.3
        bs = int(rng.choice([8, 16, 32]))
        queries = int(rng.choice([1, 16, 64]))
        heads = int(rng.choice([4, 16, 64]))
        pos = rng.integers(0, M * bs, B)
        want = _copies_one_by_one(tables, pos, bs, queries, heads)
        assert sa.walk_copies(tables, pos, bs, queries, heads) == want
        if queries == 1:
            assert _copies_by_the_plan(tables, pos, bs) == want


def _copies_by_the_plan(tables, pos, bs):
    """A decode call's DMAs as the kernel's loop issues them from the
    plan XLA packs into its tables (`sparse_attention._walk_plan`)."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    chunk = sa.index_chunk(bs)
    plan = np.asarray(jax.jit(sa._walk_plan, static_argnums=1)(
        jnp.asarray(tables), chunk))
    total = 0
    for row, p in zip(plan, pos):
        n_blocks = min(-(-(p + 1) // bs), len(row))
        for first in range(0, n_blocks, chunk):
            n, c = min(n_blocks - first, chunk), 0
            while c < n:
                size = 1 << ((row[first + c] >> sa._ID_BITS) - 1)
                assert row[first + c] >> sa._ID_BITS
                take = min(size, n - c)
                total += bin(take).count("1")
                c += take
    return total


def test_the_engine_counts_the_indexer_walks_copies(model, prompts,
                                                    monkeypatch):
    """Where the kernels serve (their gate, patched here: the programs
    still run the XLA form on the CPU), every decode step's root and
    every chunk's span carry `ik_copies`, by the walk's rule over the
    tables and positions sent; a fresh pool's tables are runs."""
    from paddle_tpu.observability import trace
    sent = []
    rule = pallas_ops._WALK_COPIES["sparse_paged_attention"]

    def record(tables, pos, *args, **kw):
        out = rule(tables, pos, *args, **kw)
        sent.append(out["ik_copies"])
        return out

    monkeypatch.setattr(pallas_ops, "_sparse_gate", lambda *a: "tpu")
    monkeypatch.setitem(pallas_ops._WALK_COPIES, "sparse_paged_attention",
                        record)
    trace.clear()
    _served(model, prompts[:2], 3)
    spans = trace.spans()
    steps = [c for name, *_, c, _, _ in spans if name == "serving.step"
             and c.get("decode_rows")]
    chunks = [c for name, *_, c, _, _ in spans if name == "serving.prefill"]
    assert steps and chunks
    assert sorted(c["ik_copies"] for c in steps + chunks) == sorted(sent)
    for c in steps:
        assert 0 < c["ik_copies"] < c["kv_blocks_walked"]


@pytest.mark.parametrize("rows,seen", [(16, [300, 300]), (8, [9]),
                                       (24, [40, 300, 512])],
                         ids=["wide", "fewer-than-k", "mixed"])
def test_the_threshold_kernel_is_top_ks_kth_value(rows, seen):
    """`topk_threshold` (interpret mode) against `lax.top_k`'s k-th value:
    rows with ties at the threshold, rows that see fewer than k finite
    scores (-inf), and tiles that see different widths."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    rng = np.random.default_rng(rows)
    x = rng.standard_normal((rows, 512)).astype(np.float32)
    x[0, :40] = 1.5                                  # ties at the top
    x[1] = np.round(x[1])                            # ties everywhere
    seen = np.asarray(seen, np.int32)
    cols = np.arange(512)[None, :]
    x = np.where(cols < np.repeat(seen, 8)[:, None], x, -np.inf)
    x[2, 5:] = -np.inf                               # fewer than k finite
    got = sa.topk_threshold(jnp.asarray(x), jnp.asarray(seen), 32,
                            interpret=True)
    want = jax.lax.top_k(jnp.asarray(x), 32)[0][:, -1:]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_counts_follow_the_path_that_serves(monkeypatch):
    """The decode step's and a chunk's positions, by the op's own gate:
    the XLA form reads the whole table, the kernels the picks."""
    planes = {"k": (40, 16, 2, 128), "v": (40, 16, 2, 128),
              "ik": (40, 16, 128)}
    kw = dict(table_cols=10, plane_shapes=planes, rows=4, heads=4,
              dtype=jnp.float32, topk=16)
    lens = [5, 70]
    assert pallas_ops.pool_positions_read(
        "sparse_paged_attention", lens, **kw) == dict(
            indexer_positions=75, selected_positions=2 * 160)
    seen = range(11, 41)
    assert pallas_ops.pool_positions_read(
        "sparse_paged_attention", [40], queries=32, real=30,
        **dict(kw, rows=1)) == dict(
            scored_pairs=sum(seen),
            selected_pairs=sum(min(n, 16) for n in seen),
            attended_pairs=30 * 160)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    assert pallas_ops.pool_positions_read(
        "sparse_paged_attention", lens, **kw) == dict(
            indexer_positions=75, selected_positions=5 + 16)
    chunk = pallas_ops.pool_positions_read(
        "sparse_paged_attention", [40], queries=32, real=30,
        **dict(kw, rows=1))
    assert chunk == dict(scored_pairs=sum(seen),
                         selected_pairs=sum(min(n, 16) for n in seen),
                         attended_pairs=sum(seen))
    assert pallas_ops.pool_blocks_read(
        "sparse_paged_attention", lens, 10, planes, 4, 4,
        jnp.float32) == 1 + 5
    assert pallas_ops.pool_positions_read(
        "paged_attention", lens, **kw) == {}


def test_an_op_whose_planes_differ_needs_a_reader_that_names_them():
    planes = {"k": (40, 16, 2, 128), "ik": (40, 16, 128)}
    with pytest.raises(ValueError, match="differ"):
        pallas_ops.pool_blocks_read("paged_attention", [5], 10, planes, 4,
                                    4, jnp.float32)


def test_the_engine_counts_what_the_step_scores_and_reads(model, prompts):
    from paddle_tpu.observability import trace
    trace.clear()
    eng, _ = _served(model, prompts[:2], 3)
    table = eng.table_cols * 16
    spans = trace.spans()
    steps = [c for name, *_, c, _, _ in spans if name == "serving.step"
             and c.get("decode_rows")]
    chunks = [c for name, *_, c, _, _ in spans if name == "serving.prefill"]
    assert steps and chunks
    for c in steps:
        # the XLA form on the CPU reads every table position of a row
        assert c["selected_positions"] == c["decode_rows"] * table
        assert c["indexer_positions"] >= c["decode_rows"] * 64
    for c in chunks:
        n, ctx = c["tokens"], c["ctx"]
        assert c["scored_pairs"] == sum(range(ctx + 1, ctx + n + 1))
        assert c["selected_pairs"] == sum(
            min(m, TOPK) for m in range(ctx + 1, ctx + n + 1))
        assert c["attended_pairs"] == n * table
