"""The latent pool's prefill kernel (ops/pallas/latent_paged_attention.py,
a chunk of more than one absorbed query row a request) against the XLA
gather `latent_paged_attention_k` that stays its parity reference,
interpreted on the CPU; and the engine serving a latent-attention model
through it the tokens it serves through the gather."""
import numpy as np
import pytest

import paddle_tpu as pt

H, W, VALUE, BS = 16, 640, 512, 16

# (pos, n, s, M) a request: a chunk of `n` tokens in a bucket of `s`
# rows at context offset `pos` over a table of `M` columns
_CASES = {
    "context-0": [(0, 24, 32, 4)],
    "mid-block": [(37, 32, 32, 6)],
    "block-aligned": [(64, 32, 32, 6)],
    "last-chunk-shorter-than-its-bucket": [(80, 11, 32, 8)],
    "ends-in-the-last-column": [(64, 32, 32, 6)],
    "rows-past-the-table": [(70, 20, 32, 6)],
    "two-rows-of-different-lengths": [(5, 30, 32, 8), (90, 13, 32, 8)],
}


def _case(rows, dtype, seed=0):
    """Requests' chunks as the engine lays them out: the table's columns
    a request owns (those that hold a position < pos + n) name blocks of
    their own, every other column block 0.  Block 0, and every position
    of the pool past a request's chunk (under its bucket's padding rows),
    hold NaN; the `clean` pool holds 0 there, for the reference."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed + sum(p for p, *_ in rows))
    s, M = rows[0][2], rows[0][3]
    owned = [min(-(-(pos + n) // BS), M) for pos, n, _, _ in rows]
    N = sum(owned) + 2
    ids = rng.permutation(np.arange(1, N))
    tables = np.zeros((len(rows), M), np.int32)
    pool = rng.randn(N, BS, W).astype(np.float32)
    at = 0
    for b, ((pos, n, _, _), k) in enumerate(zip(rows, owned)):
        tables[b, :k] = ids[at:at + k]
        at += k
        flat = pool[tables[b, :k]].reshape(-1, W)
        flat[pos + n:] = np.nan             # under the padding rows
        pool[tables[b, :k]] = flat.reshape(k, BS, W)
    q = rng.randn(len(rows), s, H, W).astype(np.float32)
    q, pool = (np.array(jnp.asarray(a, dtype).astype(jnp.float32))
               for a in (q, pool))
    pool[0] = np.nan
    clean = np.where(np.isfinite(pool), pool, 0.0).astype(np.float32)
    return q, pool, clean, tables


@pytest.mark.parametrize("tile,chunk_tokens", [
    (None, None), (8, 32)], ids=["one-tile", "tiles-and-chunks"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(_CASES))
def test_latent_prefill_kernel_walks_a_chunk_like_the_gather(
        monkeypatch, case, dtype, tile, chunk_tokens):
    """The prefill kernel (interpreted) against the XLA gather: every
    real row equal, none NaN, whatever lies under the padding rows, in
    block 0 and past what the request owns.  The second pass cuts the
    chunk into tiles of 8 query positions and the walk into chunks of
    two blocks, so that whole, edge and skipped chunks all occur (in
    interpret mode a scratch row that no copy wrote reads as NaN: a
    product that takes one in shows)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.nn_kernels import latent_paged_attention_k
    from paddle_tpu.ops.pallas import latent_paged_attention as la
    rows = _CASES[case]
    if tile is not None:
        monkeypatch.setattr(la, "_TILE_ROWS", tile * H)
        monkeypatch.setattr(la, "_CHUNK_TOKENS", chunk_tokens)
    la._latent_prefill.clear_cache()
    q, pool, clean, tables = _case(rows, dtype)
    assert la.supports(q.shape, pool.shape, VALUE, jnp.dtype(dtype))
    pos = jnp.asarray([p for p, *_ in rows], jnp.int32)
    ref = np.asarray(latent_paged_attention_k(
        jnp.asarray(q), jnp.asarray(clean), jnp.asarray(tables), pos, VALUE,
        scale=192 ** -0.5))
    out = la.latent_paged_prefill_attention(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), jnp.asarray(tables),
        pos, VALUE, scale=192 ** -0.5, interpret=True)
    la._latent_prefill.clear_cache()
    assert out.dtype == jnp.dtype(dtype)
    assert out.shape == q.shape[:3] + (VALUE,)
    tol = dict(rtol=2e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)     # p and the output, rounded
    for b, (_, n, _, _) in enumerate(rows):
        got = np.asarray(out.astype(jnp.float32))[b, :n]
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref[b, :n], **tol)


def test_latent_prefill_walk_is_the_blocks_the_kernel_touches():
    """Column j of the table made a loud block in turn (keys that win
    every score, values of 1000): the chunk's output shows whether a
    query attended that column, and `walked_blocks` counts the same
    columns.  (A block of NaN would not show: the kernel clears what is
    not finite before it multiplies anything.)"""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import latent_paged_attention as la
    rng = np.random.RandomState(7)
    M, s, heads, width, value = 12, 32, 8, 256, 128
    N = M + 1
    pool = rng.randn(N, BS, width).astype(np.float32)
    # a loud block: its keys dominate any score, its values are 1000
    pool[N - 1] = 0.0
    pool[N - 1, :, :value] = 1000.0
    q = jnp.asarray(np.abs(rng.randn(1, s, heads, width)), jnp.float32)
    clean = rng.permutation(N - 1)[:M].astype(np.int32)[None]
    for pos in (40, 100, 150):
        touched = []
        for j in range(M):
            tables = clean.copy()
            tables[0, j] = N - 1
            out = np.asarray(la.latent_paged_prefill_attention(
                q, jnp.asarray(pool), jnp.asarray(tables),
                jnp.asarray([pos], jnp.int32), value, interpret=True))
            touched.append(bool((np.abs(out) > 100).any()))
        last = min(-(-(pos + s) // BS), M)
        assert touched == [j < last for j in range(M)]
        assert sum(touched) == la.walked_blocks([pos + s], M, BS, queries=s)


def test_the_gate_serves_chunks_and_steps():
    """The shape chooses the kernel: one query row a request is the
    decode kernel's, more the prefill kernel's, at the cell's shapes
    (a 1,024-row bucket of 16 heads over rows of 640), never past what
    a tile's VMEM holds."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import latent_paged_attention as la
    bf16 = jnp.bfloat16
    pool = (33000, 16, 640)
    for s in (1, 2, 3, 32, 1024):
        assert la.supports((1, s, 16, 640), pool, 512, bf16)
    assert la.supports((1, 1024, 16, 640), pool, 512, jnp.float32)
    assert not la.supports((1, 3000, 16, 640), pool, 512, bf16)  # one tile
    assert la.supports((1, 1024, 16, 640), pool, 512, bf16, mp=2)
    with pytest.raises(ValueError, match="XLA fallback"):
        la.latent_paged_prefill_attention(
            jnp.zeros((1, 1, 16, 640), bf16), jnp.zeros((4, 16, 640), bf16),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 512)


# ------------------------------------------------------------- the engine
def test_chunked_prefill_through_the_kernel_serves_the_gathers_tokens(
        monkeypatch):
    """A tiny latent-attention model (8 heads over rows of 256, values
    of 128: shapes the kernels take) served with
    `PADDLE_TPU_PALLAS=interpret` emits the greedy tokens the XLA
    gather serves, prompts of several chunks; every ``serving.prefill``
    span says how far its program's attention followed the chunk: the
    kernel walks the blocks under its bucket's rows, the gather every
    column of the table."""
    from paddle_tpu.observability import trace
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.text.deepseek import DeepseekV3Config, \
        DeepseekV3ForCausalLM
    pt.seed(0)
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=64, hidden_size=128, num_layers=3, num_heads=8,
        intermediate_size=128, max_position_embeddings=128,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, moe_intermediate_size=64, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2)).eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (70, 9, 33, 65)]
    bs, bucket = 8, 32

    def serve():
        eng = LLMEngine(model, num_blocks=64, block_size=bs, max_running=4,
                        prefill_chunk=bucket)
        reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        eng.run()
        rids = {r.id for r in reqs}
        chunks = [s[6] for s in trace.spans()
                  if s[0] == "serving.prefill" and s[5] in rids]
        assert eng.close() == ([], [])
        return [r.generated for r in reqs], chunks, eng.table_cols

    gathered, chunks, cols = serve()
    assert sum(c["tokens"] for c in chunks) == sum(len(p) - 1
                                                   for p in prompts)
    assert len(chunks) > len(prompts)
    for c in chunks:
        assert c["kv_blocks_live"] == -(-(c["ctx"] + c["tokens"]) // bs)
        assert c["kv_blocks_walked"] == cols
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    served, chunks, _ = serve()
    assert served == gathered
    for c in chunks:
        assert c["kv_blocks_live"] == -(-(c["ctx"] + c["tokens"]) // bs)
        assert c["kv_blocks_walked"] == min(-(-(c["ctx"] + bucket) // bs),
                                            cols)
    assert any(c["kv_blocks_walked"] == c["kv_blocks_live"] for c in chunks)
