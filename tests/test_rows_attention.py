"""The whole-row attention kernel pair (ops/rows_attention.py) and the rule
that sends a ViT's attention to it (models/vit.py::attention_path).

Interpret mode, at the smallest shapes on which each assertion can fail: 13
tokens (not a multiple of 8: the padded rows of the keys-down score tile must
stay out of the softmax's sums) and two heads of 64 (one lane tile, so the
lane masks and the merge of the heads' results are on the path). The chip's
compiler sees the pair at ViT-B/16's shapes in test_chip_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_classification_pytorch_tpu.config import ModelConfig
from ddp_classification_pytorch_tpu.models.factory import model_report
from ddp_classification_pytorch_tpu.models.vit import MHA, attention_path
from ddp_classification_pytorch_tpu.obs import spans
from ddp_classification_pytorch_tpu.ops.attention import attention
from ddp_classification_pytorch_tpu.ops.rows_attention import (
    rows_attention,
    rows_supported,
)
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

B, T, H, D = 2, 13, 2, 64


def _operands(dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    qkv = jax.random.normal(ks[0], (B, T, 3 * H * D)).astype(dtype)
    cot = jax.random.normal(ks[1], (B, T, H * D)).astype(dtype)
    return qkv, cot


def _dense(qkv):
    x = qkv.reshape(B, T, 3, H, D)
    return attention(x[:, :, 0], x[:, :, 1], x[:, :, 2]).reshape(B, T, H * D)


@pytest.mark.parametrize("dtype,remat,tol", [
    ("float32", False, 2e-6), ("bfloat16", False, 2e-2),
    ("float32", True, 2e-6),
], ids=["float32", "bfloat16", "float32_under_checkpoint"])
def test_the_pair_matches_the_dense_op_forward_and_all_three_gradients(
        dtype, remat, tol):
    qkv, cot = _operands(jnp.dtype(dtype))
    rows = lambda x: rows_attention(x, H)  # noqa: E731
    if remat:
        rows = jax.checkpoint(rows)
    want, want_vjp = jax.vjp(_dense, qkv)
    got, got_vjp = jax.vjp(rows, qkv)
    assert got.shape == (B, T, H * D) and got.dtype == qkv.dtype
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    np.testing.assert_allclose(f32(got), f32(want), rtol=tol, atol=tol)
    dgot, dwant = f32(got_vjp(cot)[0]), f32(want_vjp(cot)[0])
    assert np.abs(dwant).max() > 1.0   # the comparison below is of something
    # dq, dk and dv are the three column ranges of d(qkv)
    for name, part in zip("qkv", range(3)):
        cols = slice(part * H * D, (part + 1) * H * D)
        np.testing.assert_allclose(dgot[..., cols], dwant[..., cols],
                                   rtol=tol, atol=tol, err_msg="d" + name)


def _mesh(dp, mp):
    return meshlib.make_mesh(meshlib.MeshSpec(dp, mp),
                             devices=jax.devices()[:dp * mp])


def test_a_batch_sharded_over_a_mesh_gives_the_unsharded_result():
    """The wrap a multi-device ViT takes (the TPU compiler does not partition
    a Mosaic call): per image, so two devices' halves are the whole."""
    mesh = _mesh(2, 1)
    qkv, cot = _operands(jnp.float32)
    sharded = lambda x: rows_attention(  # noqa: E731
        x, H, mesh=mesh, batch_axes=(meshlib.DATA_AXIS,))
    with mesh:
        got, vjp = jax.vjp(jax.jit(sharded), qkv)
        dgot, = vjp(cot)
    want, want_vjp = jax.vjp(_dense, qkv)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(dgot, want_vjp(cot)[0], rtol=2e-6, atol=2e-6)


def _count(path):
    return spans.counters().get(
        ("vit_attention_total", (("path", path),)), 0)


@pytest.mark.parametrize("heads,path", [(2, "rows"), (3, "dense")])
def test_the_module_takes_the_branch_its_shapes_choose_and_counts_it(
        heads, path):
    """Two heads of 64 tile the lanes: the kernel pair. Three (ViT-T's) do
    not: the dense op, as before. Both are the same function of x, and
    `vit_attention_total{path}` says which was traced."""
    mha = MHA(heads * D, heads, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, heads * D))
    variables = mha.init(jax.random.PRNGKey(1), x)
    before = {p: _count(p) for p in ("rows", "dense", "flash", "ring")}
    got = mha.apply(variables, x)
    after = {p: _count(p) for p in before}
    assert after == {**before, path: before[path] + 1}

    def by_hand(p, x):
        qkv = x @ p["qkv"]["kernel"] + p["qkv"]["bias"]
        qkv = qkv.reshape(B, T, 3, heads, D)
        out = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return out.reshape(B, T, heads * D) @ p["proj"]["kernel"] \
            + p["proj"]["bias"]

    np.testing.assert_allclose(got, by_hand(variables["params"], x),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case,kw,want", [
    ("vit_b16_at_224", dict(b=128, t=196, heads=12), ("rows", ())),
    ("vit_s16_at_384", dict(b=8, t=576, heads=6), ("rows", ())),
    ("three_heads_do_not_tile", dict(b=8, t=196, heads=3), ("dense", ())),
    ("a_row_past_vmem", dict(b=1, t=2048, heads=12), ("dense", ())),
    ("streaming_asked_for_from_1024", dict(
        b=1, t=2048, heads=12, use_flash=True, flash_min_tokens=1024),
     ("flash", ())),
    ("streaming_asked_for_but_the_row_is_short", dict(
        b=8, t=196, heads=12, use_flash=True, flash_min_tokens=1024),
     ("rows", ())),
    ("tokens_sharded", dict(b=8, t=196, heads=12, mesh=(2, 2),
                            seq_axis=meshlib.MODEL_AXIS), ("ring", ())),
    ("four_devices_batch_divides", dict(b=8, t=196, heads=12, mesh=(4, 1)),
     ("rows", (meshlib.DATA_AXIS,))),
    ("four_devices_init_batch_of_two", dict(b=2, t=196, heads=12,
                                            mesh=(4, 1)), ("dense", ())),
    ("a_mesh_of_one_device", dict(b=2, t=196, heads=12, mesh=(1, 1)),
     ("rows", ())),
])
def test_the_shape_rule(case, kw, want):
    kw = dict(kw)
    mesh = _mesh(*kw.pop("mesh")) if "mesh" in kw else None
    assert attention_path(d=D, dtype=jnp.bfloat16, mesh=mesh, **kw) == want


def test_the_vmem_bound_is_about_eleven_hundred_tokens_at_vit_b16():
    fits = [t for t in range(64, 4096, 64) if rows_supported(t, 12, 64, 2)]
    assert fits == list(range(64, fits[-1] + 64, 64)) and 1024 <= fits[-1] < 1280
    # wider heads: one a tile; narrower: four; a width no tile holds: none
    assert rows_supported(196, 6, 128, 2) and rows_supported(196, 16, 32, 2)
    assert not rows_supported(196, 8, 48, 2)


@pytest.mark.parametrize("arch,size,want", [
    ("vit_b16", 224, "rows"), ("vit_t16", 224, "dense")])
def test_the_set_up_line_says_which_core_the_blocks_take(arch, size, want):
    report = model_report(ModelConfig(arch=arch, dtype="bfloat16"))
    assert report.built(128, None, size) == {"vit_attention": want}
