"""Pallas flash attention vs the XLA sdpa reference, interpret mode on CPU.

Mirrors the reference's flash-attn unit tests
(test/legacy_test/test_flash_attention.py): forward allclose vs the
naive softmax path, gradients allclose via vjp, causal and full.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops import call_raw
from paddle_tpu.ops.nn_kernels import sdpa_k


def _rand_qkv(rng, B, L, H, D, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, L, H, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, L, H, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 256, 4, 32)])
def test_flash_forward_matches_sdpa(causal, shape):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, *shape)
    out = fa.flash_attention(q, k, v, is_causal=causal, interpret=True)
    ref = sdpa_k(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_sdpa(causal):
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, 1, 128, 2, 64)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, is_causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(sdpa_k(q, k, v, is_causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_under_jit():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, 1, 128, 2, 64)
    f = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, is_causal=True, interpret=True))
    out = f(q, k, v)
    ref = sdpa_k(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_registry_override_falls_back_on_cpu():
    # without PADDLE_TPU_PALLAS=interpret the CPU backend must use XLA sdpa
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, 1, 64, 2, 16)
    out = call_raw("sdpa", q, k, v, None, is_causal=True)
    ref = sdpa_k(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_registry_override_interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, 2, 128, 2, 64)
    out = call_raw("sdpa", q, k, v, None, is_causal=True)
    ref = sdpa_k(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal_cross_length():
    # bottom-right-aligned causal (KV-cache prefill: Lk > Lq) must match the
    # XLA path's jnp.tril(..., lk - lq) alignment
    rng = np.random.default_rng(5)
    B, H, D = 1, 2, 64
    q = jnp.asarray(rng.standard_normal((B, 64, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, 128, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, 128, H, D)), jnp.float32)
    out = fa.flash_attention(q, k, v, is_causal=True, interpret=True)
    ref = sdpa_k(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_supports_gate():
    s = (2, 128, 4, 64)
    assert fa.supports(s, s, None, jnp.float32)
    assert not fa.supports(s, s, object(), jnp.float32)   # weird mask obj
    # ragged (round 3): handled by internal padding now
    assert fa.supports((2, 100, 4, 64), s, None, jnp.float32)
    assert not fa.supports(s, s, None, jnp.int32)


# ----------------------------------------------------- round-3 extensions
def _ref_gqa(q, k, v, mask=None, is_causal=False):
    return sdpa_k(q, k, v, mask=mask, is_causal=is_causal)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_repeat(causal):
    # kv heads grouped inside the kernel == repeat_interleave + dense
    rng = np.random.default_rng(6)
    B, L, H, Hkv, D = 2, 128, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    out = fa.flash_attention(q, k, v, is_causal=causal, interpret=True)
    kr = jnp.repeat(k, H // Hkv, axis=2)
    vr = jnp.repeat(v, H // Hkv, axis=2)
    ref = sdpa_k(q, kr, vr, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_grads():
    rng = np.random.default_rng(7)
    B, L, H, Hkv, D = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, is_causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, H // Hkv, axis=2)
        vr = jnp.repeat(v, H // Hkv, axis=2)
        return jnp.sum(jnp.sin(sdpa_k(q, kr, vr, is_causal=True)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mask_kind", ["bool_padding", "additive_full",
                                       "bool_full_bh"])
def test_flash_masks(mask_kind):
    rng = np.random.default_rng(8)
    B, L, H, D = 2, 128, 2, 64
    q, k, v = _rand_qkv(rng, B, L, H, D)
    if mask_kind == "bool_padding":
        # (B, 1, 1, Lk) key-padding mask, rows broadcast
        lens = np.array([100, 77])
        m = (np.arange(L)[None, :] < lens[:, None])
        mask = jnp.asarray(m)[:, None, None, :]
    elif mask_kind == "additive_full":
        mask = jnp.asarray(
            np.where(rng.random((B, 1, L, L)) < 0.8, 0.0, -1e9), jnp.float32)
    else:
        mask = jnp.asarray(rng.random((B, H, L, L)) < 0.9)
    assert fa.supports(q.shape, k.shape, mask, q.dtype)
    out = fa.flash_attention(q, k, v, mask=mask, interpret=True)
    ref = sdpa_k(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_mask_grads():
    rng = np.random.default_rng(9)
    B, L, H, D = 1, 128, 2, 32
    q, k, v = _rand_qkv(rng, B, L, H, D)
    lens = np.array([90])
    mask = jnp.asarray((np.arange(L)[None, :] < lens[:, None]))[:, None,
                                                                None, :]

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, mask=mask, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(sdpa_k(q, k, v, mask=mask)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(1, 100, 2, 64), (2, 257, 2, 32),
                                   (1, 7, 2, 64)])
def test_flash_ragged_lens(shape):
    # non-block-divisible seq lens: padded internally, cols masked
    rng = np.random.default_rng(10)
    q, k, v = _rand_qkv(rng, *shape)
    for causal in (False, True):
        out = fa.flash_attention(q, k, v, is_causal=causal, interpret=True)
        ref = sdpa_k(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_ragged_grads():
    rng = np.random.default_rng(11)
    q, k, v = _rand_qkv(rng, 1, 100, 2, 32)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, is_causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(sdpa_k(q, k, v, is_causal=True)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_decode_shape():
    # Lq=1 single-token decode against a KV cache with a padding mask
    rng = np.random.default_rng(12)
    B, Lk, H, D = 2, 128, 4, 64
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Lk, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Lk, H, D)), jnp.float32)
    lens = np.array([64, 100])
    mask = jnp.asarray((np.arange(Lk)[None, :] < lens[:, None]))[:, None,
                                                                 None, :]
    out = fa.flash_attention(q, k, v, mask=mask, interpret=True)
    ref = sdpa_k(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_supports_gate_round3():
    s = (2, 128, 4, 64)
    skv = (2, 128, 2, 64)   # GQA now supported
    assert fa.supports(s, skv, None, jnp.float32)
    assert not fa.supports(s, (2, 128, 3, 64), None, jnp.float32)  # 4%3
    assert fa.supports((2, 100, 4, 64), s[:1] + (100,) + s[2:], None,
                       jnp.float32)  # ragged now supported
    mask = jnp.zeros((2, 1, 128, 128), jnp.float32)
    assert fa.supports(s, s, mask, jnp.float32)
    assert not fa.supports(s, s, object(), jnp.float32)  # weird mask obj
    assert not fa.supports(s, s, None, jnp.int32)


# -------------------------------------------- the kernels under a fleet mesh
# Mosaic kernels cannot be partitioned by GSPMD: under a multi-device mesh
# the dispatch wrappers run them per shard inside jax.shard_map (batch on
# dp, heads on mp) or take the XLA path by a rule of supports().
@pytest.fixture
def fleet_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod
    prev = dict(mesh_mod._state)
    yield mesh_mod
    mesh_mod._state.update(prev)


def test_supports_gate_mesh_shards():
    s, skv = (4, 128, 4, 64), (4, 128, 2, 64)
    assert fa.supports(s, skv, None, jnp.float32, shards=(2, 2))
    assert not fa.supports(s, skv, None, jnp.float32, shards=(1, 4))  # Hkv
    assert not fa.supports(s, skv, None, jnp.float32, shards=(8, 1))  # B
    assert not fa.supports(s, skv, None, jnp.float32, shards=None)
    m3 = jnp.zeros((4, 128, 128), jnp.float32)
    assert fa.supports(s, s, m3, jnp.float32)
    assert not fa.supports(s, s, m3, jnp.float32, shards=(2, 1))
    assert not fa.supports(s, s, None, jnp.float16)   # Mosaic refuses f16


@pytest.mark.parametrize("degrees", [(2, 1, 2), (1, 1, 4), (4, 1, 1)])
def test_sdpa_override_under_mesh_matches_xla(monkeypatch, fleet_mesh,
                                              degrees):
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    fleet_mesh.build_mesh(*degrees)
    rng = np.random.default_rng(11)
    B, L, H, Hkv, D = 4, 64, 8, 4, 32
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    mask = jnp.asarray(rng.standard_normal((B, 1, L, L)), jnp.float32)
    assert plo._shards(plo._mesh_split()) == (degrees[0], degrees[2])

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, mask=mask, is_causal=True) ** 2)

    sh = fleet_mesh.sharding("dp", None, "mp", None)
    args = [jax.device_put(a, sh) for a in (q, k, v)]
    got = jax.jit(jax.value_and_grad(
        lambda *a: loss(plo.sdpa_with_flash, *a), argnums=(0, 1, 2)))(*args)
    ref = jax.value_and_grad(
        lambda *a: loss(sdpa_k, *a), argnums=(0, 1, 2))(q, k, v)
    assert "shard_map" in str(jax.make_jaxpr(
        lambda *a: plo.sdpa_with_flash(*a, is_causal=True))(q, k, v))
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_sdpa_override_mesh_gates_to_xla(monkeypatch, fleet_mesh):
    """Heads that do not divide mp, and a live axis the kernel cannot be
    split over (pp outside a pipeline stage body), take the XLA path."""
    from paddle_tpu.ops import pallas as plo
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(12)
    q, k, v = _rand_qkv(rng, 2, 32, 6, 16)
    for degrees in [(1, 1, 4), (1, 2, 2)]:
        fleet_mesh.build_mesh(*degrees)
        jaxpr = str(jax.make_jaxpr(
            lambda *a: plo.sdpa_with_flash(*a, is_causal=True))(q, k, v))
        assert "shard_map" not in jaxpr and "pallas_call" not in jaxpr


def test_paged_override_under_mp_mesh(monkeypatch, fleet_mesh):
    from paddle_tpu.ops import pallas as plo
    from paddle_tpu.ops.nn_kernels import paged_attention_k
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    fleet_mesh.build_mesh(1, 1, 2)
    rng = np.random.RandomState(0)
    B, H, Hkv, D, bs, N, M = 3, 8, 4, 128, 8, 12, 4
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(N, bs, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(N, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(rng.permutation(N)[:B * M].reshape(B, M),
                         jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    sh = fleet_mesh.sharding(None, None, "mp", None)
    got = jax.jit(plo.paged_attention_with_pallas)(
        jax.device_put(q, sh), jax.device_put(kp, sh),
        jax.device_put(vp, sh), tables, pos)
    ref = paged_attention_k(q, kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # kv heads that do not divide mp: gated, never raised from a trace
    fleet_mesh.build_mesh(1, 1, 8)
    jaxpr = str(jax.make_jaxpr(plo.paged_attention_with_pallas)(
        q, kp, vp, tables, pos))
    assert "pallas_call" not in jaxpr
