"""The main path's Pallas kernels, compiled for a DESCRIBED TPU v5e.

Interpret mode runs a kernel as jnp ops and cannot see what the chip's
compiler refuses (block shapes off the (8, 128) tiling, kernels that GSPMD
cannot partition).  libtpu can compile for a topology that is described and
not attached, so these tests catch that on the CPU, about two seconds each.

The topology is described inside a module-scoped fixture — never at import:
only one process may load libtpu at a time, and every xdist worker imports
every test file.  All such tests live in THIS file, so one worker owns the
library.  Nothing here runs a kernel: a compile that passes is not a chip run.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import latent_paged_attention as la
from paddle_tpu.ops.pallas import paged_attention as pa

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _flash_loss(q, k, v):
    o = fa.flash_attention(q, k, v, is_causal=True)
    return jnp.sum(o.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("H,Hkv", [(16, 16), (12, 2)],
                         ids=["mha16x128", "gqa12over2x128"])
def test_flash_fwd_bwd_compiles(one_chip, H, Hkv):
    s = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                          sharding=one_chip)
    q, kv = s((4, 1024, H, 128)), s((4, 1024, Hkv, 128))
    assert fa.supports(q.shape, kv.shape, None, q.dtype, v_shape=kv.shape,
                       is_causal=True)
    text = _compiled_text(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                          q, kv, kv)
    assert KERNEL in text


@pytest.mark.parametrize("dtype,H,Hkv,B,N,M", [
    (jnp.bfloat16, 16, 16, 8, 280, 34), (jnp.float32, 16, 16, 8, 280, 34),
    (jnp.bfloat16, 12, 2, 8, 280, 34), (jnp.bfloat16, 6, 1, 8, 280, 34),
    (jnp.bfloat16, 16, 16, 32, 2600, 128),
    (jnp.float32, 16, 16, 32, 2600, 128),
    (jnp.bfloat16, 32, 8, 32, 2600, 128),
    (jnp.bfloat16, 64, 8, 64, 16700, 512)],
    ids=["gpt1.3B-bf16", "gpt1.3B-f32", "gqa12over2-bf16",
         "gqa6over1-bf16", "cell-bf16", "cell-f32", "cell-gqa32over8-bf16",
         "solar-cell-gqa64over8-bf16"])
def test_paged_decode_compiles(one_chip, dtype, H, Hkv, B, N, M):
    """gpt3-1.3B serving shapes, 16 heads x 128 in 16-token blocks: 8
    slots, and the benchmark's cells (32 slots, a table of 128 columns
    over a pool of 2,600 blocks; the hybrid cell's one GQA layer, 64
    slots of 64 heads over 8, a table of 512 columns over 16,700
    blocks); 6 heads over ONE kv head is 12 over 2 cut by `mp` 2: a
    block of 16 rows, one 16-bit tile."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = s((B, 1, H, 128), dtype)
    pool = s((N, 16, Hkv, 128), dtype)
    assert pa.supports(q.shape, pool.shape, dtype)
    text = _compiled_text(pa.paged_decode_attention, q, pool, pool,
                          s((B, M), jnp.int32), s((B,), jnp.int32))
    assert KERNEL in text


@pytest.mark.parametrize("H,window,N", [
    (72, 512, 3104), (48, None, 28000), (72, 8, 3104)],
    ids=["window-72over8", "full-48over8", "window8-72over8"])
def test_paged_decode_of_two_kinds_compiles(one_chip, H, window, N):
    """The window-and-full cell's two decode shapes, 32 slots over a
    table of 1,024 columns: 72 query heads over 8 kv heads under a band
    of 512 (groups of 9; 72 rows of bfloat16 are no whole 16-row tiles)
    from the window group's 3,104 blocks, and 48 over 8 (groups of 6)
    over the whole context.  The banded kernel has a name of its own."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = s((32, 1, H, 128), jnp.bfloat16)
    pool = s((N, 16, 8, 128), jnp.bfloat16)
    assert pa.supports(q.shape, pool.shape, jnp.bfloat16)
    text = _compiled_text(
        functools.partial(pa.paged_decode_attention, window=window),
        q, pool, pool, s((32, 1024), jnp.int32), s((32,), jnp.int32))
    assert KERNEL in text
    assert ("paged_window_decode_attention" in text) == (window is not None)


@pytest.mark.parametrize("S,H,Hkv,dtype,N,M,window", [
    (1024, 48, 8, jnp.bfloat16, 28000, 1024, None),
    (1024, 72, 8, jnp.bfloat16, 3104, 1024, 512),
    (512, 64, 8, jnp.bfloat16, 16700, 512, None),
    (512, 16, 16, jnp.bfloat16, 2600, 128, None),
    (512, 16, 16, jnp.float32, 2600, 128, None),
    (32, 72, 8, jnp.bfloat16, 3104, 1024, 512),
    (32, 6, 1, jnp.bfloat16, 280, 34, None)],
    ids=["laguna-full-48over8", "laguna-window-72over8",
         "solar-gqa64over8", "gpt1.3B-bf16", "gpt1.3B-f32",
         "smallest-bucket-window", "mqa6over1-smallest-bucket"])
def test_paged_prefill_compiles(one_chip, S, H, Hkv, dtype, N, M, window):
    """The prefill kernel at the cells' chunk shapes, one request a
    program: the window-and-full cell's 1,024 rows of 48 heads over 8
    over a table of 1,024 columns and of 72 over 8 under the band of
    512, the hybrid cell's 512 rows of 64 over 8, the dense cells' 512
    rows of 16 heads (a 16-bit pool's kv heads come apart in pairs
    through a 32-bit view, a float32 pool's by a strided load), the
    ladder's smallest bucket, and one kv head (a shard of few).  The
    names are the prefill kernel's own: a decode reader's pattern that
    matched one would divide decode work by prefill time."""
    import re
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = s((1, S, H, 128), dtype)
    pool = s((N, 16, Hkv, 128), dtype)
    assert pa.supports(q.shape, pool.shape, dtype)
    text = _compiled_text(
        functools.partial(pa.paged_prefill_attention, window=window),
        q, pool, pool, s((1, M), jnp.int32), s((1,), jnp.int32))
    assert KERNEL in text
    name = "paged_prefill_attention" if window is None \
        else "paged_window_prefill_attention"
    assert name in text
    for pattern in (r"paged_decode|paged_attention",
                    r"(?<!latent_)paged_decode_attention",
                    r"paged_window_decode_attention",
                    r"latent_paged_decode_attention"):
        assert not re.search(pattern, name)


def test_paged_prefill_gate_refuses_what_the_compiler_refuses(one_chip):
    """A 16-bit pool of an odd number (> 1) of kv heads: two rows share
    a 32-bit word and Mosaic loads 32-bit rows alone with a stride, so
    the body takes kv heads apart in pairs and an odd one has no
    partner; `supports` sends it to the XLA gather.  The same heads in
    float32 compile, and so does a chunk of any length (3 rows)."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    shape = (1, 64, 6, 128), (280, 16, 3, 128)
    assert not pa.supports(*shape, jnp.bfloat16)
    with pytest.raises(ValueError, match="XLA fallback"):
        pa.paged_prefill_attention(
            s(shape[0], jnp.bfloat16), s(shape[1], jnp.bfloat16),
            s(shape[1], jnp.bfloat16), s((1, 34), jnp.int32),
            s((1,), jnp.int32))
    assert pa.supports(*shape, jnp.float32)
    q, pool = s(shape[0], jnp.float32), s(shape[1], jnp.float32)
    assert KERNEL in _compiled_text(
        pa.paged_prefill_attention, q, pool, pool, s((1, 34), jnp.int32),
        s((1,), jnp.int32))
    assert KERNEL in _compiled_text(
        pa.paged_prefill_attention, s((1, 3, 6, 128), jnp.float32), pool,
        pool, s((1, 34), jnp.int32), s((1,), jnp.int32))


def test_a_decode_call_through_the_gate_is_the_decode_kernel_alone(
        one_chip, monkeypatch):
    """One query row a request takes the decode kernel and nothing of
    the prefill path: the override's program for a decode step of the
    window-and-full cell is, to the byte of its traced form, the decode
    kernel's own on `pos + 1` (which this PR left the parent's:
    CHANGES.md)."""
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setattr(plo, "_mode", lambda: "tpu")
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = s((32, 1, 48, 128), jnp.bfloat16)
    pool = s((28000, 16, 8, 128), jnp.bfloat16)
    tables, pos = s((32, 1024), jnp.int32), s((32,), jnp.int32)
    through = _compiled_text(
        lambda q, k, v, t, p: plo.paged_attention_with_pallas(q, k, v, t, p),
        q, pool, pool, tables, pos)
    assert "paged_decode_attention" in through
    assert "prefill" not in through
    # (a compiled text names source lines; the traced programs do not)
    traced = [str(jax.make_jaxpr(f)(q, pool, pool, tables, pos)) for f in (
        lambda q, k, v, t, p: plo.paged_attention_with_pallas(q, k, v, t, p),
        lambda q, k, v, t, p: pa.paged_decode_attention(q, k, v, t, p + 1))]
    assert traced[0] == traced[1]
    # ... and more rows a request take the prefill kernel, no threshold
    chunk = _compiled_text(
        lambda q, k, v, t, p: plo.paged_attention_with_pallas(q, k, v, t, p),
        s((1, 32, 48, 128), jnp.bfloat16), pool, pool,
        s((1, 1024), jnp.int32), s((1,), jnp.int32))
    assert "paged_prefill_attention" in chunk
    assert "paged_decode_attention" not in chunk


@pytest.mark.parametrize("dtype,B,N,M", [
    (jnp.bfloat16, 32, 32000, 1024), (jnp.float32, 8, 512, 64)],
    ids=["cell-bf16", "small-f32"])
def test_latent_paged_decode_compiles(one_chip, dtype, B, N, M):
    """The latent cell's decode shapes: 32 slots of 16 heads over rows of
    [c 512 | k_rope 64 | 64 zeros] in 16-token blocks, a table of 1,024
    columns (16,384 positions) riding scalar prefetch."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q, pool = s((B, 1, 16, 640), dtype), s((N, 16, 640), dtype)
    assert la.supports(q.shape, pool.shape, 512, dtype)
    text = _compiled_text(
        lambda q, pool, t, n: la.latent_paged_decode_attention(
            q, pool, t, n, 512, scale=192 ** -0.5),
        q, pool, s((B, M), jnp.int32), s((B,), jnp.int32))
    assert KERNEL in text


@pytest.mark.parametrize("S,dtype", [
    (1024, jnp.bfloat16), (32, jnp.bfloat16), (512, jnp.float32)],
    ids=["cell-largest-bucket", "cell-smallest-bucket", "f32"])
def test_latent_paged_prefill_compiles_through_the_gate(
        one_chip, monkeypatch, S, dtype):
    """The latent cell's chunk shapes, one request a program: a bucket
    of S rows of 16 heads over rows of [c 512 | k_rope 64 | 64 zeros]
    from a pool of 33,000 blocks, a table of 1,024 columns.  Through the
    op's override, as the model calls it: more than one query row a
    request is the prefill kernel, under a name of its own that the
    decode reader's pattern does not match; a float32 pool takes a
    smaller tile (`prefill_tile`), within the same VMEM."""
    import re
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setattr(plo, "_mode", lambda: "tpu")
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q, pool = s((1, S, 16, 640), dtype), s((33000, 16, 640), dtype)
    assert la.supports(q.shape, pool.shape, 512, dtype)
    text = _compiled_text(
        lambda q, pool, t, p: plo.latent_paged_attention_with_pallas(
            q, pool, t, p, 512, scale=192 ** -0.5),
        q, pool, s((1, 1024), jnp.int32), s((1,), jnp.int32))
    assert KERNEL in text
    assert "latent_paged_prefill_attention" in text
    assert "latent_paged_decode_attention" not in text
    from benchmark.metrics import latent_paged_roofline
    assert not re.search(latent_paged_roofline.PATTERN,
                         "latent_paged_prefill_attention")


# what `paged_prefill_attention` traced to before its walk was shared
# with the latent kernel (sha256 of the jaxpr's text, first 16 digits;
# a jaxpr names no source file, so any checkout hashes the same under
# the same jax)
_PAGED_PREFILL_JAXPR = {
    "gpt1.3B": ((512, 16, 16, 2600, 128, None), "cb29921960d3b86b"),
    "solar-gqa64over8": ((512, 64, 8, 16700, 512, None), "caefe38b4372ff57"),
    "laguna-full-48over8": ((1024, 48, 8, 28000, 1024, None),
                            "642e9b2b4d5bb0fa"),
    "laguna-window-72over8": ((1024, 72, 8, 3104, 1024, 512),
                              "903f5eae2cae1f04"),
}


@pytest.mark.parametrize("case", list(_PAGED_PREFILL_JAXPR))
def test_the_shared_walk_leaves_the_kv_prefill_kernel_as_it_was(case):
    """The K/V prefill kernel at the dense, hybrid and window-and-full
    cells' chunk shapes traces to the program it traced to before the
    walk became `PrefillWalk`, to the byte: the cells that run it run
    what they ran."""
    import hashlib
    (S, H, Hkv, N, M, window), want = _PAGED_PREFILL_JAXPR[case]
    s = jax.ShapeDtypeStruct
    q, pool = s((1, S, H, 128), jnp.bfloat16), s((N, 16, Hkv, 128),
                                                  jnp.bfloat16)
    text = str(jax.make_jaxpr(functools.partial(
        pa.paged_prefill_attention, window=window))(
            q, pool, pool, s((1, M), jnp.int32), s((1,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("rows", [192, 6144], ids=["decode", "chunk"])
def test_dropless_experts_compile_as_grouped_products(one_chip, rows):
    """`moe_dropless` at the latent cell's widths (64 experts of 2048 x
    1408, 6 a token): a decode step's 32 x 6 assignments and a prefill
    chunk's 1,024 x 6.  The grouped products stay grouped (no [N, E, C]
    dispatch tensor): the program's temporaries are the sorted rows."""
    from paddle_tpu.incubate.nn import moe_dropless
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = jnp.bfloat16
    n = rows // 6
    compiled = jax.jit(moe_dropless).lower(
        s((n, 2048), bf16), s((n, 6), jnp.int32), s((n, 6), jnp.float32),
        s((64, 2048, 1408), bf16), s((64, 2048, 1408), bf16),
        s((64, 1408, 2048), bf16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 40 * rows * 2048


def test_kda_decode_step_compiles_and_updates_the_pool_in_place(one_chip):
    """The delta-rule step at the hybrid cell's widths (64 rows, 64 heads
    of 128 x 128 float32 state, 64 slots): a Mosaic kernel whose state
    output aliases the donated pool, with no temporary the size of it."""
    from paddle_tpu.ops.pallas import kda
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f32 = jnp.float32
    vec, pool = s((64, 64, 128), f32), s((64, 64, 128, 128), f32)
    assert kda.supports(vec.shape, pool.shape)
    compiled = jax.jit(kda.kda_decode_step, donate_argnums=(5,)).lower(
        vec, vec, vec, vec, s((64, 64), f32), pool, s((64,), jnp.int32),
        s((64,), jnp.bool_)).compile()
    assert KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 64 * 64 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 64 * 64 * 128 * 4 * 8


@pytest.mark.parametrize("rows", [512, 4096], ids=["decode", "chunk"])
def test_a_share_of_the_experts_compiles_as_grouped_products(one_chip, rows):
    """`moe_dropless(first=0)` over 40 held experts of 4096 x 1280 under a
    router of 320, 8 a token: the rows no group covers are masked, the
    products stay grouped."""
    from paddle_tpu.incubate.nn import moe_dropless
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = jnp.bfloat16
    n = rows // 8
    compiled = jax.jit(functools.partial(moe_dropless, first=0)).lower(
        s((n, 4096), bf16), s((n, 8), jnp.int32), s((n, 8), jnp.float32),
        s((40, 4096, 1280), bf16), s((40, 4096, 1280), bf16),
        s((40, 1280, 4096), bf16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 40 * rows * 4096


def test_supports_refuses_what_the_compiler_refuses(one_chip):
    """float16: Mosaic has no f16 vector load on this chip — supports()
    must say so, for both kernels, and the compiler must agree."""
    s = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float16,
                          sharding=one_chip)
    q = s((4, 1024, 16, 128))
    assert not fa.supports(q.shape, q.shape, None, jnp.float16)
    assert not pa.supports((8, 1, 16, 128), (280, 16, 16, 128), jnp.float16)
    with pytest.raises(Exception, match="Invalid vector type"):
        _compiled_text(lambda q, k, v: fa.flash_attention(
            q, k, v, is_causal=True), q, q, q)


def test_flash_through_dispatch_under_2x2_mesh(topo, monkeypatch):
    """`sdpa_with_flash` under a dp2 x mp2 fleet mesh: bare, the compiler
    says "Mosaic kernels cannot be automatically partitioned"; the
    dispatch must wrap the kernel in a shard_map."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.ops import pallas as plo
    # code that asks jax.devices() sees the CPU here: steer it in the test
    monkeypatch.setattr(plo, "_mode", lambda: "tpu")
    # the meshes set below live in a copy that monkeypatch throws away
    monkeypatch.setattr(mesh_mod, "_state", dict(mesh_mod._state))
    mesh_mod.set_mesh(Mesh(np.asarray(topo.devices).reshape(2, 1, 2),
                           mesh_mod.AXES))
    sh = NamedSharding(mesh_mod.get_mesh(), P("dp", None, "mp", None))
    q = jax.ShapeDtypeStruct((8, 1024, 16, 128), jnp.bfloat16,
                             sharding=sh)

    def loss(q, k, v):
        o = plo.sdpa_with_flash(q, k, v, is_causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert KERNEL in text
    # the paged kernel with an mp-sharded pool (BlockPool.shard_)
    mesh_mod.set_mesh(Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                           mesh_mod.AXES))
    hs = NamedSharding(mesh_mod.get_mesh(), P(None, None, "mp", None))
    rep = NamedSharding(mesh_mod.get_mesh(), P())
    s = jax.ShapeDtypeStruct
    for B, N, M in [(8, 280, 34), (32, 2600, 128)]:
        text = _compiled_text(
            plo.paged_attention_with_pallas,
            s((B, 1, 16, 128), jnp.bfloat16, sharding=hs),
            s((N, 16, 16, 128), jnp.bfloat16, sharding=hs),
            s((N, 16, 16, 128), jnp.bfloat16, sharding=hs),
            s((B, M), jnp.int32, sharding=rep),
            s((B,), jnp.int32, sharding=rep))
        assert KERNEL in text


def test_ring_block_fwd_bwd_compiles(one_chip):
    """One KV-ring step's kernels (distributed/ring_attention.py)."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    x = s((2, 512, 16, 128), jnp.bfloat16)
    fwd = _compiled_text(
        lambda q, k, v: fa.flash_block_fwd(q, k, v, True), x, x, x)
    bwd = _compiled_text(
        lambda q, k, v, o, lse, do: fa.flash_block_bwd(
            q, k, v, o, lse, do, True),
        x, x, x, x, s((2, 16, 512), jnp.float32), x)
    assert KERNEL in fwd and KERNEL in bwd


# the sparse-attention cell's pool: 36,500 blocks of 16 positions, a
# table of 3,200 columns (51,200 positions), 12 rows
_SPARSE_N, _SPARSE_M, _SPARSE_B = 36500, 3200, 12


@pytest.mark.parametrize("S", [1, 2048, 32],
                         ids=["decode", "cell-chunk", "smallest-bucket"])
def test_sparse_paged_attention_compiles_through_the_gate(
        one_chip, monkeypatch, S):
    """`sparse_paged_attention` at the sparse cell's shapes, as the model
    calls it: 32 query heads over 4 kv heads of 128, 16 indexer heads
    over one cached key padded to 128 lanes, top 2,048.  A decode step
    is the indexer's decode kernel, XLA's top-k and the attention over
    the picked rows alone; a chunk the indexer's prefill kernel and the
    K/V prefill walk with each query's threshold.  Each kernel carries a
    name of its own that no reader of another kernel matches."""
    import re
    from benchmark.metrics import indexer_roofline, sparse_prefill_roofline
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setattr(plo, "_mode", lambda: "tpu")
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = jnp.bfloat16
    B = _SPARSE_B if S == 1 else 1
    pool = s((_SPARSE_N, 16, 4, 128), bf16)
    text = _compiled_text(
        lambda q, k, v, ik, qi, w, t, p: plo.sparse_paged_attention_with_pallas(
            q, k, v, ik, qi, w, t, p, topk=2048, scale=128 ** -0.5),
        s((B, S, 32, 128), bf16), pool, pool, s((_SPARSE_N, 16, 128), bf16),
        s((B, S, 16, 128), bf16), s((B, S, 16), jnp.float32),
        s((B, _SPARSE_M), jnp.int32), s((B,), jnp.int32))
    names = ({"indexer_decode_scores"} if S == 1
             else {"indexer_prefill_scores", "sparse_topk_threshold",
                   "sparse_prefill_attention"})
    for name in names:
        assert name in text
    readers = {"indexer_decode_scores": indexer_roofline,
               "indexer_prefill_scores": indexer_roofline,
               "sparse_prefill_attention": sparse_prefill_roofline}
    others = (r"paged_decode|paged_attention",
              r"(?<!latent_)paged_decode_attention",
              r"paged_window_decode_attention",
              r"latent_paged_decode_attention", r"^mosaic:(?!paged)")
    for name, reader in readers.items():
        assert re.search(reader.PATTERN, name)
        for mod in set(readers.values()) - {reader}:
            assert not re.search(mod.PATTERN, name)
        for pattern in others:
            assert not re.search(pattern, name)
