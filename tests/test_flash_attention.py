"""Flash-attention kernel (interpret mode on CPU) vs the dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

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
    """Same params: ViT(use_flash=True) == ViT(use_flash=False), in four
    blocks: each calls the kernel as the next does."""
    from ddp_classification_pytorch_tpu.models.vit import build_vit

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 64, 64, 3)), jnp.float32)
    dense = build_vit("vit_t16_d4", num_classes=5, dtype=jnp.float32)
    flash = build_vit("vit_t16_d4", num_classes=5, dtype=jnp.float32,
                      use_flash=True)
    vs = dense.init(jax.random.PRNGKey(0), x, train=False)
    np.testing.assert_allclose(
        np.asarray(flash.apply(vs, x, train=False)),
        np.asarray(dense.apply(vs, x, train=False)), atol=1e-4)


# ------------------------------------------- the fused backward (PR 36) --

# T = 256 in blocks of 64: a 4 x 4 grid of tiles, so every case streams.
# b, heads, kv_heads, d, dv, dr (0: no second part of the scores), and the
# keyword arguments of the op
_BACKWARD_CASES = {
    "non_causal": (2, 2, 2, 32, 32, 0, {}),
    "causal": (2, 2, 2, 32, 32, 0, {"causal": True}),
    # keys 80 back: q-block 3 sees kv-blocks 2-3 whole or in part, 1 by its
    # edge (cols 113-127 of rows 192-206), 0 not at all
    "window_dead_and_edge_tiles": (2, 2, 2, 32, 32, 0,
                                   {"causal": True, "window": 80}),
    "grouped_kv_heads_4": (1, 8, 2, 32, 32, 0, {"causal": True}),
    "value_narrower_than_scores": (2, 2, 2, 32, 16, 0, {"causal": True}),
    "latent_one_rope_head_under_two_kv_heads": (
        2, 4, 2, 16, 24, 8, {"causal": True, "window": 150}),
}


def _backward_counts():
    """(fused, split) of `flash_backward_total`: process totals."""
    from ddp_classification_pytorch_tpu.obs import spans

    return tuple(spans.counters().get(
        ("flash_backward_total", (("path", path),)), 0)
        for path in ("fused", "split"))


@pytest.fixture
def fa64(monkeypatch):
    """The module (ops/__init__ re-exports a same-named function) with its
    blocks shrunk to 64."""
    import importlib

    fa = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: 64)
    return fa


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("case", list(_BACKWARD_CASES) + ["lse_cotangent"])
def test_backward_matches_the_dense_ops_gradients(case, path, fa64, monkeypatch):
    """dQ, dK, dV (and dQ_r, dK_r) of the backward against `jax.grad` of the
    (T, T) op, on the one fused kernel and, with the VMEM budget shrunk so
    that no whole-T accumulator fits it, on the two-kernel split, which has
    to agree with the fused kernel to the order of its sums;
    `flash_backward_total` counts the path taken once for the one attention
    call traced."""
    fa = fa64
    t = 256
    if case == "lse_cotangent":
        # ring attention's building block: the cotangent of the row
        # logsumexp folds into the kernels' Δ
        b, h, h_kv, d, dv, dr, kw = 2, 2, 2, 32, 32, 0, {"causal": True}
    else:
        b, h, h_kv, d, dv, dr, kw = _BACKWARD_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    args = [jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, h_kv, d)),
            jax.random.normal(ks[2], (b, t, h_kv, dv))]
    if dr:
        args += [jax.random.normal(ks[3], (b, t, h, dr)),
                 jax.random.normal(ks[4], (b, t, 1, dr))]  # ONE rotary head
    cot = jax.random.normal(ks[5], (b, t, h, dv))
    cot_lse = jax.random.normal(ks[6], (b, h, t))

    def dense(q, k, v, *rope):
        if case != "lse_cotangent":
            return (attention(q, k, v, **kw, **dict(zip(
                ("q_rope", "k_rope"), rope))) * cot).sum()
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return ((attention(q, k, v, causal=True) * cot).sum()
                + (jax.nn.logsumexp(s, axis=-1) * cot_lse).sum())

    def flash(q, k, v, *rope):
        if case != "lse_cotangent":
            return (fa.flash_attention(q, k, v, **kw, **dict(zip(
                ("q_rope", "k_rope"), rope))) * cot).sum()
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True)
        return (out * cot).sum() + (lse * cot_lse).sum()

    every = tuple(range(len(args)))
    want = jax.grad(dense, argnums=every)(*args)
    fused0, split0 = _backward_counts()
    fused = jax.grad(flash, argnums=every)(*args)
    assert _backward_counts() == (fused0 + 1, split0)
    got = fused
    if path == "split":
        monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._TILE_VMEM)
        got = jax.grad(flash, argnums=every)(*args)
        assert _backward_counts() == (fused0 + 1, split0 + 1)
        for a, f in zip(got, fused):
            np.testing.assert_allclose(a, f, rtol=1e-6, atol=1e-6)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5)


def test_the_fused_backward_is_taken_by_what_its_accumulators_weigh(fa64):
    """The path is a byte count of the shapes and nothing else: an f32
    accumulator and a double-buffered output block for the whole T of K, V
    (and K_r), lanes padded to 128, beside the tiles' 16 MiB."""
    fa = fa64
    mib = 2 ** 20
    # the three decoder cells at 8,192 tokens in bf16, and a ViT's 196
    assert fa._fused_vmem_bytes(8192, (128, 128, 64), 2) == (24 + 16) * mib
    assert fa._fused_vmem_bytes(8192, (128, 128), 2) == (16 + 16) * mib
    assert fa._fused_vmem_bytes(8192, (64, 64), 2) == (16 + 16) * mib
    assert fa._fused_vmem_bytes(196, (64, 64), 2) < 17 * mib
    # 128-wide K and V in bf16: fused up to 40 thousand tokens, split beyond
    assert fa._fused_vmem_bytes(40960, (128, 128), 2) <= fa._VMEM_BUDGET
    assert fa._fused_vmem_bytes(49152, (128, 128), 2) > fa._VMEM_BUDGET


@pytest.mark.parametrize("sizes,dtype,want", [
    ({}, "bfloat16", "fused"),                        # SmallThinker as published
    ({"attention": "mla", "rope_dim": 64, "v_head_dim": 128}, "bfloat16", "fused"),
    ({"head_dim": 64, "conv_layout": (1, 1, 1, 0)}, "float32", "fused"),
    ({"seq_len": 65536}, "bfloat16", "split"),        # past the VMEM budget
    ({"seq_len": 512}, "bfloat16", None),             # the dense op's rows
    ({"conv_layout": (1,)}, "bfloat16", None),        # no attention layer
], ids=["gqa_8k", "latent_8k", "one_attention_layer_in_four", "rows_of_65536",
        "rows_of_512", "convolutions_only"])
def test_the_decoder_names_its_backward_from_its_sizes(sizes, dtype, want):
    """What the `[trainer] set-up:` line says (`flash_backward=`) before any
    step is traced: the kernels' own byte count over the decoder's sizes."""
    from ddp_classification_pytorch_tpu.config import DecoderConfig
    from ddp_classification_pytorch_tpu.models.decoder_lm import (
        flash_backward_path)

    assert flash_backward_path(DecoderConfig(**sizes), dtype, 1024) == want
