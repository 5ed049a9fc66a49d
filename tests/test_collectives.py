"""Explicit shard_map DDP step vs the auto-sharded jit step: same math.

Runs both on the 8-device CPU mesh from identical initial state and batch;
parameters after one step must agree to float tolerance (reduction order may
differ), proving the auto-sharded path really does compute DDP semantics.
"""

import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.parallel.collectives import (
    build_ddp_model,
    make_shard_map_train_step,
)
from ddp_classification_pytorch_tpu.train.schedule import build_optimizer
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_train_step


def _tiny_cfg():
    cfg = get_preset("baseline")
    cfg.data.dataset = "synthetic"
    cfg.data.image_size = 16
    cfg.data.num_classes = 4
    cfg.data.batch_size = 16
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    return cfg


def test_shard_map_step_matches_auto_sharded():
    cfg = _tiny_cfg()
    mesh = meshlib.make_mesh()
    rng = np.random.default_rng(0)
    images = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, 4, 16).astype(np.int32)

    with mesh:
        # auto-sharded path
        model_a, tx_a, state_a = create_train_state(cfg, mesh, steps_per_epoch=4)
        auto_step = make_train_step(cfg, model_a, tx_a)
        ia = jax.device_put(images, meshlib.batch_sharding(mesh))
        la = jax.device_put(labels, meshlib.batch_sharding(mesh))
        state_a, metrics_a = auto_step(state_a, ia, la)

        # explicit shard_map path (axis-name BN), same init seed
        model_b = build_ddp_model(cfg)
        p_rng, d_rng = jax.random.split(jax.random.PRNGKey(cfg.run.seed))
        variables = model_b.init(  # identical init stream to create_train_state
            {"params": p_rng, "dropout": d_rng},
            jnp.zeros((2, 16, 16, 3)), train=False)
        tx_b = build_optimizer(cfg.optim, 4)
        from ddp_classification_pytorch_tpu.train.state import TrainState

        state_b = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=jax.device_put(variables["params"], meshlib.replicated(mesh)),
            batch_stats=jax.device_put(variables["batch_stats"], meshlib.replicated(mesh)),
            opt_state=jax.jit(tx_b.init)(variables["params"]),
        )
        ddp_step = make_shard_map_train_step(cfg, model_b, tx_b, mesh)
        state_b, metrics_b = ddp_step(state_b, ia, la)

    # same loss and same updated params (reduction order may differ slightly)
    assert float(metrics_a["loss"]) == pytest.approx(float(metrics_b["loss"]), rel=1e-4)
    assert float(metrics_a["top1"]) == pytest.approx(float(metrics_b["top1"]), abs=1e-6)
    pa = jax.tree_util.tree_leaves(jax.device_get(state_a.params))
    pb = jax.tree_util.tree_leaves(jax.device_get(state_b.params))
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)
    # BN batch_stats must match too (global-batch stats == pmean'd stats)
    sa = jax.tree_util.tree_leaves(jax.device_get(state_a.batch_stats))
    sb = jax.tree_util.tree_leaves(jax.device_get(state_b.batch_stats))
    for a, b in zip(sa, sb):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_hybrid_mesh_two_tier_layout_and_training():
    """make_hybrid_mesh: slice-major data axis (2 'slices' x 2 DP x 2 MP on
    the virtual mesh) drives the same jitted train step unchanged."""
    import numpy as np

    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    mesh = meshlib.make_hybrid_mesh(
        meshlib.MeshSpec(4, 2), dcn_data_parallel=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}

    cfg = _tiny_cfg()
    cfg.data.image_size, cfg.data.batch_size = 32, 8
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        step = make_train_step(cfg, model, tx)
        rng = np.random.default_rng(0)
        images = jax.device_put(
            rng.normal(size=(8, 32, 32, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh))
        labels = jax.device_put(
            rng.integers(0, 4, 8).astype(np.int32),
            meshlib.batch_sharding(mesh))
        state, metrics = step(state, images, labels)
        assert np.isfinite(float(metrics["loss"]))
