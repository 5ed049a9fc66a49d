"""The flash kernels under the block-diffusion rule (two streams in one
row, ops/flash_attention.py `diffusion`) against the dense op under a mask
built from the four rules: forward, the fused backward and the split one, the
tiles the kernels walk, and what `flash_tiles_total` counts."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_classification_pytorch_tpu.obs import spans
from ddp_classification_pytorch_tpu.ops.attention import attention, diffusion_mask

fa = importlib.import_module("ddp_classification_pytorch_tpu.ops.flash_attention")


def four_rules(length: int, block: int) -> np.ndarray:
    """(2L, 2L) bool from the rules as written, pair by pair."""
    blk = np.arange(length) // block
    cc = blk[:, None] >= blk[None, :]
    nc = blk[:, None] > blk[None, :]
    nn = blk[:, None] == blk[None, :]
    return np.block([[cc, np.zeros_like(cc)], [nc, nn]])


@pytest.mark.parametrize("length,block", [(8, 4), (64, 4), (64, 32), (96, 8)])
def test_the_dense_mask_is_the_four_rules(length, block):
    np.testing.assert_array_equal(
        np.asarray(diffusion_mask(2 * length, block)), four_rules(length, block))


def _tiles(mask: str):
    c = spans.counters()
    return tuple(c.get(("flash_tiles_total", (("mask", mask), ("state", s))), 0)
                 for s in ("live", "skipped"))


# L = 256 a stream in tiles of 128: a 4 x 4 square of tiles, the compact kv
# dimension 3 steps long (B <= 128) or 4 (B = 256: a diffusion block of two
# tiles, the clean diagonal run reaching past the diagonal)
@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("block,heads,kv_heads", [(4, 2, 2), (32, 4, 2), (256, 2, 1)])
def test_kernels_match_the_dense_op_under_the_four_rules(block, heads, kv_heads,
                                                         path, monkeypatch):
    length, tile, d = 256, 128, 32
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: tile)
    if path == "split":
        monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._TILE_VMEM)
    ks = jax.random.split(jax.random.PRNGKey(block), 4)
    q = jax.random.normal(ks[0], (1, 2 * length, heads, d))
    k = jax.random.normal(ks[1], (1, 2 * length, kv_heads, d))
    v = jax.random.normal(ks[2], (1, 2 * length, kv_heads, d))
    cot = jax.random.normal(ks[3], (1, 2 * length, heads, d))
    mask = four_rules(length, block)

    def dense(q, k, v):
        g = heads // kv_heads
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2)) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, g, axis=2)) * cot).sum()

    def flash(q, k, v):
        return (fa.flash_attention(q, k, v, diffusion_block=block) * cot).sum()

    # the op's own dense path reads the same mask
    np.testing.assert_allclose(
        (attention(q, k, v, diffusion_block=block) * cot).sum(), dense(q, k, v),
        rtol=1e-5)
    before = _tiles("block_diffusion")
    want, want_grads = jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v)
    got, got_grads = jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5)
    # the counter: tiles of the square in which the dense mask has a True,
    # once a kernel launched (forward + fused backward, or + the split pair)
    n = 2 * length // tile
    live = int(mask.reshape(n, tile, n, tile).any(axis=(1, 3)).sum())
    launches = 2 if path == "fused" else 3
    after = _tiles("block_diffusion")
    assert (after[0] - before[0], after[1] - before[1]) == (
        launches * live, launches * (n * n - live))


@pytest.mark.parametrize("length,block,tile", [(8192, 4, 512), (8192, 4, 1024),
                                               (512, 32, 128), (512, 256, 128)])
def test_the_compact_walk_visits_every_live_tile_once(length, block, tile):
    """Every q block's steps through the compact kv dimension visit exactly
    the kv blocks `_diffusion_live` calls live, each once, and the dead steps
    re-request a block of the walk (no fetch of their own). At the cell's
    sizes: 288 of 1,024 tiles of 512 (28 %); 80 of 256 tiles of 1,024."""
    diffusion = (length, block)
    n = 2 * length // tile
    steps = fa._diffusion_steps(tile, diffusion)
    live = np.asarray(fa._diffusion_live(tile, tile, np.arange(n)[:, None],
                                         np.arange(n)[None, :], diffusion))
    if (length, tile) == (8192, 512):
        assert (int(live.sum()), n * n, steps) == (288, 1024, 17)
    if (length, tile) == (8192, 1024):
        assert (int(live.sum()), n * n) == (80, 256)
    # elementwise in its arguments: every (q block, step) pair at once
    kk, on = (np.asarray(x) for x in fa._diffusion_step(
        tile, np.arange(n)[:, None], np.arange(steps)[None, :], diffusion))
    for j in range(n):
        assert sorted(kk[j][on[j]]) == list(np.flatnonzero(live[j])), j
        assert set(kk[j][~on[j]]) <= set(kk[j][on[j]]), j
    # the split backward's dK/dV kernel: a dead step's fetch is a live block
    qq = np.asarray(fa._diffusion_q_block(
        tile, np.arange(n)[:, None], np.arange(n)[None, :], diffusion))
    for j in range(n):
        rows = np.flatnonzero(live[:, j])
        np.testing.assert_array_equal(qq[j][rows], rows)
        assert set(qq[j]) <= set(rows), j


def test_shapes_the_kernels_do_not_tile_take_the_dense_op():
    assert fa.diffusion_supported(2 * 8192, 4)
    assert fa.diffusion_supported(2 * 256, 32)
    assert not fa.diffusion_supported(2 * 8192, 48)     # 512 and 48: neither divides
    assert not fa.diffusion_supported(2 * 520, 4)       # a stream the kernels do not tile
    q = jnp.ones((1, 48, 1, 8))
    with pytest.raises(ValueError, match="two streams"):
        fa.flash_attention(q, q, q, diffusion_block=5)
    with pytest.raises(ValueError, match="two streams"):
        fa.flash_attention(q, q, q, causal=True, diffusion_block=4)
