"""GPipe executor: pipelined == sequential, values AND gradients, on the
8-device CPU mesh (shard_map + ppermute + psum — the code path a TPU pod
runs over ICI)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.ops.pipeline import gpipe, _stage_apply
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib


def _block_apply(p, x):
    """Toy homogeneous block: x @ W + b, gelu."""
    return jax.nn.gelu(x @ p["w"] + p["b"])


def _stacked(depth=8, ch=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(scale=0.3, size=(depth, ch, ch)), jnp.float32),
        "b": jnp.asarray(rng.normal(scale=0.1, size=(depth, ch)), jnp.float32),
    }


def _x(b=8, t=4, ch=16, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(b, t, ch)), jnp.float32)


@pytest.mark.parametrize("stages,micro", [(2, 2), (4, 4), (8, 2)])
def test_gpipe_matches_sequential(stages, micro):
    mesh = meshlib.make_mesh(
        meshlib.MeshSpec(len(jax.devices()) // stages, stages))
    params, x = _stacked(), _x()
    seq = _stage_apply(_block_apply, params, x)
    pipe = jax.jit(lambda p, x: gpipe(
        _block_apply, p, x, mesh=mesh, axis_name=meshlib.MODEL_AXIS,
        microbatches=micro))(params, x)
    np.testing.assert_allclose(np.asarray(pipe), np.asarray(seq), atol=1e-5)


def test_gpipe_gradients_match_sequential():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    params, x = _stacked(), _x()

    def loss_seq(p):
        return (_stage_apply(_block_apply, p, x) ** 2).mean()

    def loss_pipe(p):
        out = gpipe(_block_apply, p, x, mesh=mesh,
                    axis_name=meshlib.MODEL_AXIS, microbatches=2)
        return (out ** 2).mean()

    g_seq = jax.grad(loss_seq)(params)
    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_pipe[k]), np.asarray(g_seq[k]), atol=1e-5, err_msg=k)


def test_gpipe_single_stage_degenerates_to_sequential():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()), 1))
    params, x = _stacked(), _x()
    out = gpipe(_block_apply, params, x, mesh=mesh,
                axis_name=meshlib.MODEL_AXIS, microbatches=4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_stage_apply(_block_apply, params, x)),
        atol=1e-6)


def test_gpipe_validates_divisibility():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    params, x = _stacked(depth=6), _x()
    with pytest.raises(ValueError, match="not divisible"):
        gpipe(_block_apply, params, x, mesh=mesh,
              axis_name=meshlib.MODEL_AXIS, microbatches=2)
    params, x = _stacked(), _x(b=6)
    with pytest.raises(ValueError, match="batch"):
        gpipe(_block_apply, params, x, mesh=mesh,
              axis_name=meshlib.MODEL_AXIS, microbatches=4)


def _pp_cfg(mp=2, micro=2):
    from ddp_classification_pytorch_tpu.config import get_preset

    cfg = get_preset("baseline")
    cfg.model.arch = "vit_t16_d4"
    cfg.model.dtype = "float32"
    cfg.data.image_size = 64  # 16 tokens
    cfg.data.num_classes = 4
    cfg.data.batch_size = 8
    cfg.parallel.model_axis = mp
    cfg.parallel.pipeline_microbatches = micro
    return cfg


def test_gpipe_vit_forward_matches_single_stage():
    """Same params through a 4-stage pipeline and through the degenerate
    1-stage sequential path must agree."""
    import jax

    from ddp_classification_pytorch_tpu.models.pipeline_vit import GPipeViT

    mesh_pp = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    mesh_seq = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()), 1))
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 64, 64, 3)), jnp.float32)
    pp = GPipeViT("vit_t16_d4", 4, mesh_pp, 2, dtype=jnp.float32)
    seq = GPipeViT("vit_t16_d4", 4, mesh_seq, 2, dtype=jnp.float32)
    vs = pp.init(jax.random.PRNGKey(0), x)
    out_pp = jax.jit(lambda v, x: pp.apply(v, x, train=False))(vs, x)
    out_seq = seq.apply(vs, x, train=False)
    np.testing.assert_allclose(
        np.asarray(out_pp), np.asarray(out_seq), atol=2e-4)


def test_gpipe_vit_train_step_e2e():
    """Full jitted train step: dp×pp mesh, stacked params stage-sharded."""
    import jax

    from ddp_classification_pytorch_tpu.parallel.mesh import MODEL_AXIS
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    cfg = _pp_cfg(mp=2, micro=2)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 2))
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        # stacked block params actually sharded over stages
        leaf = state.params["blocks"]["attn"]["qkv"]["kernel"]
        assert leaf.sharding.spec[0] == MODEL_AXIS
        step = make_train_step(cfg, model, tx)
        rng = np.random.default_rng(0)
        images = jax.device_put(
            rng.normal(size=(8, 64, 64, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh))
        labels = jax.device_put(
            rng.integers(0, 4, 8).astype(np.int32),
            meshlib.batch_sharding(mesh))
        losses = []
        for _ in range(3):
            state, metrics = step(state, images, labels)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_pipeline_flag_rejects_unsupported_configs():
    import pytest as _pytest

    from ddp_classification_pytorch_tpu.models.factory import build_model

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    cfg = _pp_cfg().model
    cfg.arch = "resnet50"
    with _pytest.raises(ValueError, match="requires a ViT"):
        build_model(cfg, 4, mesh=mesh, pipeline_microbatches=2)
    cfg.arch = "vit_t16_d4"
    cfg.head = "nested"
    with _pytest.raises(ValueError, match="head='fc' or 'arcface'"):
        build_model(cfg, 4, mesh=mesh, pipeline_microbatches=2)
    # arcface is SUPPORTED since r4 (GPipeArcFaceViT — the dp×tp×pp
    # composition, tests/test_three_axis_pipeline.py)
    cfg.head = "arcface"
    from ddp_classification_pytorch_tpu.models.pipeline_vit import (
        GPipeArcFaceViT,
    )

    assert isinstance(
        build_model(cfg, 4, mesh=mesh, pipeline_microbatches=2),
        GPipeArcFaceViT)
    cfg.head = "fc"
    cfg.dropout = 0.1
    with _pytest.raises(ValueError, match="dropout"):
        build_model(cfg, 4, mesh=mesh, pipeline_microbatches=2)
