"""Flash-attention kernel (interpret mode on CPU) vs the dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_classification_pytorch_tpu.ops.attention import attention
from ddp_classification_pytorch_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, t=128, h=2, d=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("t", [128, 196, 256])
def test_flash_matches_dense(t):
    """Aligned (128/256) and odd-T single-block fallback (196) forwards.
    Multi-block streaming is pinned below with a shrunken block size."""
    q, k, v = _qkv(t=t)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(attention(q, k, v)), atol=1e-5)


def test_flash_multiblock_forward_and_backward(monkeypatch):
    """Shrink the block size to 64 so T=256 genuinely streams 4 blocks:
    exercises the forward's online-softmax rescaling across kv steps and
    BOTH backward kernels' scratch init/accumulate/write paths
    (kk==0 / += / kk==nk-1), which full-size blocks only hit at T ≥ 1024."""
    import importlib

    # the ops package re-exports a same-named function, so plain imports
    # resolve to it instead of the module
    fa = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: 64)
    q, k, v = _qkv(t=256)
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v)),
        np.asarray(attention(q, k, v)), atol=1e-5)
    gf = jax.grad(lambda q, k, v: (fa.flash_attention(q, k, v) ** 2).mean(),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: (attention(q, k, v) ** 2).mean(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("t", [128, 196])
def test_flash_causal_matches_dense(t):
    q, k, v = _qkv(t=t)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(attention(q, k, v, causal=True)), atol=1e-5)


def test_flash_causal_gradients_multiblock(monkeypatch):
    """Block 64 at T=256 → blocks fully below, straddling, and fully above
    the diagonal all occur, in the forward and BOTH backward kernels."""
    import importlib

    fa = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: 64)
    q, k, v = _qkv(t=256)
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, causal=True)),
        np.asarray(attention(q, k, v, causal=True)), atol=1e-5)
    gf = jax.grad(
        lambda q, k, v: (fa.flash_attention(q, k, v, causal=True) ** 2).mean(),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(
        lambda q, k, v: (attention(q, k, v, causal=True) ** 2).mean(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flash_unsupported_t_falls_back_to_dense():
    """Prime T above 512 cannot tile cleanly; the public entry point must
    route to the dense op (same values, gradients still defined)."""
    q, k, v = _qkv(b=1, t=521, h=1, d=16)
    with pytest.warns(UserWarning, match="T=521 .* dense op"):
        out = flash_attention(q, k, v)  # reported by name, never silent
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(attention(q, k, v)), atol=1e-5)
    g = jax.grad(lambda q: (flash_attention(q, k, v) ** 2).mean())(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_flash_bf16_close_to_f32_dense():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2)


@pytest.mark.parametrize("t", [128, 196, 256])
def test_flash_gradients_match_dense(t):
    """Single-block backward over aligned (128/256) and odd-T (196) shapes.
    The multi-block accumulation paths are pinned separately below with a
    shrunken block size (full-scale blocks only split at T ≥ 1024, too slow
    for interpret mode)."""
    q, k, v = _qkv(t=t)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v) ** 2).mean()

    def loss_dense(q, k, v):
        return (attention(q, k, v) ** 2).mean()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flash_bf16_gradients_close_to_f32_dense():
    """The backward kernels keep MXU operands in the input dtype (bf16 in
    the ViT recipe) with f32 accumulation — pin that path against the f32
    dense gradients with a bf16-appropriate tolerance."""
    q, k, v = _qkv(dtype=jnp.bfloat16)
    gf = jax.grad(lambda q, k, v: (flash_attention(q, k, v) ** 2)
                  .astype(jnp.float32).mean(), argnums=(0, 1, 2))(q, k, v)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    gd = jax.grad(lambda q, k, v: (attention(q, k, v) ** 2).mean(),
                  argnums=(0, 1, 2))(q32, k32, v32)
    for a, b in zip(gf, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=5e-2)


def test_flash_under_jit_and_vmap_free_shapes():
    q, k, v = _qkv(b=1, t=128, h=1, d=64)
    out = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(attention(q, k, v)), atol=1e-5)


def test_vit_with_flash_matches_dense_vit():
    """Same params: ViT(use_flash=True) == ViT(use_flash=False)."""
    from ddp_classification_pytorch_tpu.models.vit import build_vit

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 64, 64, 3)), jnp.float32)
    dense = build_vit("vit_t16", num_classes=5, dtype=jnp.float32)
    flash = build_vit("vit_t16", num_classes=5, dtype=jnp.float32,
                      use_flash=True)
    vs = dense.init(jax.random.PRNGKey(0), x, train=False)
    np.testing.assert_allclose(
        np.asarray(flash.apply(vs, x, train=False)),
        np.asarray(dense.apply(vs, x, train=False)), atol=1e-4)
