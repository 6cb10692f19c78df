"""The paged prefill kernel (ops/pallas/paged_attention.py, a chunk of
more than one query row a request) against the XLA gather that stays its
parity reference, interpreted on the CPU; and the engine serving through
it what it serves through the gather."""
import numpy as np
import pytest

import paddle_tpu as pt


# (H, Hkv, s, n, pos, M, window, dtype): a chunk of `n` tokens in a
# bucket of `s` rows at context offset `pos` over a table of `M` columns
_PREFILL_CASES = {
    "mha16-s8-pos0": (16, 16, 8, 5, 0, 4, None, "float32"),
    "mha16-s64-midblock": (16, 16, 64, 50, 37, 8, None, "bfloat16"),
    "mha16-ends-in-the-last-column": (16, 16, 64, 64, 64, 8, None,
                                      "float32"),
    "gqa64over8-s64-aligned": (64, 8, 64, 64, 160, 16, None, "bfloat16"),
    "gqa64over8-rows-past-the-table": (64, 8, 64, 40, 50, 6, None,
                                       "bfloat16"),
    "gqa48over8-s64-midblock": (48, 8, 64, 40, 37, 8, None, "float32"),
    "gqa48over8-s8-window512-pos0": (48, 8, 8, 8, 0, 4, 512, "float32"),
    "gqa72over8-s64-window8": (72, 8, 64, 64, 100, 12, 8, "float32"),
    "gqa72over8-s64-window8-past-the-table": (72, 8, 64, 20, 60, 6, 8,
                                              "bfloat16"),
    "gqa72over8-s64-window512": (72, 8, 64, 33, 1000, 68, 512, "bfloat16"),
    "mqa6over1-s32": (6, 1, 32, 17, 21, 5, None, "bfloat16"),
}


def _prefill_case(H, Hkv, s, n, pos, M, window, dtype, seed=0):
    """A request's chunk as the engine lays it out: the table's columns
    the request owns (those that hold a position < pos + n) name blocks
    of their own, every other column block 0; under a window the
    columns before the band are block 0 too (the pool took those blocks
    back).  Block 0 and every block wholly under the bucket's padding
    rows hold NaN; `clean` pools hold 0 there, for the reference."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed + H + s + pos)
    bs, D = 16, 128
    owned = min(-(-(pos + n) // bs), M)
    behind = 0 if window is None else max(pos - (window - 1), 0) // bs
    N = M + 2
    tables = np.zeros((1, M), np.int32)
    tables[0, behind:owned] = rng.permutation(
        np.arange(1, N))[:owned - behind]
    q = rng.randn(1, s, H, D).astype(np.float32)
    k = rng.randn(N, bs, Hkv, D).astype(np.float32)
    v = rng.randn(N, bs, Hkv, D).astype(np.float32)
    q, k, v = (np.array(jnp.asarray(a, dtype).astype(jnp.float32))
               for a in (q, k, v))
    clean_k, clean_v = k.copy(), v.copy()
    k[0] = v[0] = np.nan
    clean_k[0] = clean_v[0] = 0.0
    return q, (k, v), (clean_k, clean_v), tables


@pytest.mark.parametrize("tile,chunk_bytes", [
    (None, None), (16, 16 * 1024)], ids=["one-tile", "tiles-and-chunks"])
@pytest.mark.parametrize("case", list(_PREFILL_CASES))
def test_paged_prefill_kernel_walks_a_chunk_like_the_gather(
        monkeypatch, case, tile, chunk_bytes):
    """The prefill kernel (interpreted) against the XLA gather: every
    real row equal, none NaN, whatever lies under the padding rows, past
    what the request owns and before the band.  The second pass cuts the
    chunk in tiles of 16 query positions and the walk in chunks of one or
    two blocks, so that whole chunks, edge chunks and skipped chunks all
    occur (in interpret mode a scratch row that no copy wrote reads as
    NaN: a product that takes one in shows)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.nn_kernels import paged_attention_k
    from paddle_tpu.ops.pallas import paged_attention as pa
    H, Hkv, s, n, pos, M, window, dtype = _PREFILL_CASES[case]
    if tile is not None:
        monkeypatch.setattr(pa, "_TILE_ROWS", tile * H)
        monkeypatch.setattr(pa, "_CHUNK_BYTES", chunk_bytes)
        pa._paged_prefill.clear_cache()
    q, pools, clean, tables = _prefill_case(H, Hkv, s, n, pos, M, window,
                                            dtype)
    assert pa.supports(q.shape, pools[0].shape, jnp.dtype(dtype))
    at = jnp.asarray([pos], jnp.int32)
    ref = np.asarray(paged_attention_k(
        jnp.asarray(q), *(jnp.asarray(a) for a in clean),
        jnp.asarray(tables), at, window=window))
    out = pa.paged_prefill_attention(
        jnp.asarray(q, dtype), *(jnp.asarray(a, dtype) for a in pools),
        jnp.asarray(tables), at, interpret=True, window=window)
    if tile is not None:
        pa._paged_prefill.clear_cache()
    assert out.dtype == jnp.dtype(dtype) and out.shape == q.shape
    got = np.asarray(out.astype(jnp.float32))[0, :n]
    assert np.isfinite(got).all()
    tol = dict(rtol=2e-5, atol=3e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)     # p and the output, rounded
    np.testing.assert_allclose(got, ref[0, :n], **tol)


def test_prefill_walk_is_the_blocks_the_kernel_touches():
    """Column j of the table made a block of NaNs in turn: the chunk's
    output shows whether the walk reached that column, with and without
    a band, and `walked_blocks` counts the same columns."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(7)
    M, bs, H, D, s = 12, 16, 4, 128, 32
    N = M + 1
    k = rng.randn(N, bs, H, D).astype(np.float32)
    v = rng.randn(N, bs, H, D).astype(np.float32)
    k[N - 1] = np.nan       # a NaN key shows in every row that scores it
    q = jnp.asarray(rng.randn(1, s, H, D), jnp.float32)
    clean = rng.permutation(N - 1)[:M].astype(np.int32)[None]
    for pos, window in ((40, None), (100, None), (100, 24), (150, 8)):
        touched = []
        for j in range(M):
            tables = clean.copy()
            tables[0, j] = N - 1
            out = np.asarray(pa.paged_prefill_attention(
                q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
                jnp.asarray([pos], jnp.int32), interpret=True,
                window=window))
            touched.append(bool(np.isnan(out).any()))
        first = 0 if window is None else max(pos - (window - 1), 0) // bs
        last = min(-(-(pos + s) // bs), M)
        assert touched == [first <= j < last for j in range(M)]
        assert sum(touched) == pa.walked_blocks([pos + s], M, bs, window,
                                                queries=s)
    # one query a row is the decode kernel's rule
    assert pa.walked_blocks([41], M, bs, 24, queries=1) \
        == pa.walked_blocks([41], M, bs, 24) == 2


# ------------------------------------------------------------- the engine
def _gpt():
    from paddle_tpu.text import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=256, num_layers=2, num_heads=2,
        intermediate_size=64, max_position_embeddings=128,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_parallel=False))


def _solar_open2():
    from paddle_tpu.text.solar_open2 import (SolarOpen2Config,
                                             SolarOpen2ForCausalLM)
    return SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=64, hidden_size=128, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=128, gqa_layers=[0, 4, 8],
        kda_num_heads=4, kda_head_dim=32, kda_gate_rank=16,
        moe_intermediate_size=64, n_routed_experts=16, held_experts=(0, 4),
        num_experts_per_tok=2, max_position_embeddings=128,
        dtype="float32"))


def _laguna():
    from paddle_tpu.text.laguna import LagunaConfig, LagunaForCausalLM
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    return LagunaForCausalLM(LagunaConfig(
        vocab_size=64, hidden_size=128, num_layers=5, num_heads=4,
        intermediate_size=128, max_position_embeddings=128, num_kv_heads=2,
        head_dim=128, layer_types=kinds * 2,
        num_attention_heads_per_layer=[4, 6, 6, 6, 4], sliding_window=8,
        mlp_only_layers=[0], num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=64, shared_expert_intermediate_size=64,
        held_experts=[0, 4], dtype="float32"))


@pytest.mark.parametrize("build", [_gpt, _solar_open2, _laguna],
                         ids=["gpt", "solar_open2", "laguna"])
def test_chunked_prefill_through_the_kernel_serves_the_gathers_tokens(
        monkeypatch, build):
    """Prompts of several chunks (and, under Laguna's window of 8,
    several bands: the window group's early blocks have gone home
    before the later chunks) served with `PADDLE_TPU_PALLAS=interpret`
    emit the tokens the XLA gather serves, and every ``serving.prefill``
    span says how far its program's attention followed the chunk: the
    kernel walks the blocks under its bucket's rows and nothing else,
    the gather every column of the table (of the band's, under a
    window)."""
    from paddle_tpu.observability import trace
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import LLMEngine
    pt.seed(0)
    model = build().eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (70, 9, 33, 65)]
    bs = 8

    def serve():
        eng = LLMEngine(model, num_blocks=64, block_size=bs, max_running=4,
                        prefill_chunk=32)
        reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        eng.run()
        rids = {r.id for r in reqs}
        chunks = [s[6] for s in trace.spans()
                  if s[0] == "serving.prefill" and s[5] in rids]
        windows = [grp.window for grp in eng.pool.groups]
        assert eng.close() == ([], [])
        return [r.generated for r in reqs], chunks, windows, eng.table_cols

    def first(ctx, window):     # the first block a chunk at `ctx` sees
        return 0 if window is None else max(ctx - (window - 1), 0) // bs

    gathered, chunks, windows, cols = serve()
    # (the prefill lane splits a step's 32 tokens over the requests)
    assert sum(c["tokens"] for c in chunks) == sum(len(p) - 1
                                                   for p in prompts)
    assert len(chunks) > len(prompts)
    for c in chunks:
        n, ctx, bucket = c["tokens"], c["ctx"], 32
        assert c["kv_blocks_live"] == sum(
            -(-(ctx + n) // bs) - first(ctx, w) for w in windows)
        assert c["kv_blocks_walked"] == sum(
            cols if w is None else min(cols, pa.band_blocks(w + bucket, bs))
            for w in windows)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    served, chunks, _, _ = serve()
    assert served == gathered
    for c in chunks:
        n, ctx, bucket = c["tokens"], c["ctx"], 32
        live = sum(-(-(ctx + n) // bs) - first(ctx, w) for w in windows)
        padded = sum(-(-(ctx + bucket) // bs) - first(ctx, w)
                     for w in windows)
        assert c["kv_blocks_live"] == live
        assert c["kv_blocks_walked"] == padded
        assert (padded == live) == (-(-(ctx + n) // bs)
                                    == -(-(ctx + bucket) // bs))
    assert any(c["kv_blocks_walked"] == c["kv_blocks_live"] for c in chunks)
    # what a reader would print: the share of the read blocks that were
    # live (missing to 100: the blocks under the buckets' padding rows)
    share = 100.0 * sum(c["kv_blocks_live"] for c in chunks) \
        / sum(c["kv_blocks_walked"] for c in chunks)
    assert 50.0 < share < 100.0
