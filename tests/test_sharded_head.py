"""Class-sharded ArcFace CE vs the dense reference, on the 8-device mesh.

The class dimension is this framework's long-context analogue (SURVEY §5):
these tests pin the partial-FC-style sharded loss — values, gradients, and
top-k counts — against ops/arcface.py::arc_margin_logits + dense CE.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.ops.arcface import arc_margin_logits
from ddp_classification_pytorch_tpu.ops.sharded_head import arc_margin_ce_sharded
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib


def _setup(b=8, d=16, c=12, seed=0):
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, b), jnp.int32)
    return feats, weight, labels


def _dense_loss(feats, weight, labels, **kw):
    logits = arc_margin_logits(feats, weight, labels, **kw)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


@pytest.mark.parametrize("mp", [2, 4])
@pytest.mark.parametrize("easy_margin", [True, False])
def test_sharded_ce_matches_dense(mp, easy_margin):
    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()) // mp, mp))
    feats, weight, labels = _setup()
    loss, t1, t3 = jax.jit(
        lambda f, w, l: arc_margin_ce_sharded(
            f, w, l, mesh, meshlib.MODEL_AXIS, batch_axis=meshlib.DATA_AXIS,
            easy_margin=easy_margin)
    )(feats, weight, labels)
    dense = _dense_loss(feats, weight, labels, easy_margin=easy_margin)
    np.testing.assert_allclose(float(loss), float(dense), atol=1e-5)

    # top-k counts vs a dense top-k with the same semantics
    logits = arc_margin_logits(feats, weight, labels, easy_margin=easy_margin)
    _, top3 = jax.lax.top_k(logits, 3)
    hits = np.asarray(top3) == np.asarray(labels)[:, None]
    assert float(t1) == hits[:, :1].sum()
    assert float(t3) == hits.sum()


def test_sharded_ce_gradients_match_dense():
    mp = 4
    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()) // mp, mp))
    feats, weight, labels = _setup()

    def sharded(f, w):
        return arc_margin_ce_sharded(
            f, w, labels, mesh, meshlib.MODEL_AXIS,
            batch_axis=meshlib.DATA_AXIS)[0]

    gf = jax.jit(jax.grad(sharded, argnums=(0, 1)))(feats, weight)
    gd = jax.grad(lambda f, w: _dense_loss(f, w, labels), argnums=(0, 1))(
        feats, weight)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_sharded_ce_rejects_indivisible_classes():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    feats, weight, labels = _setup(c=10)
    with pytest.raises(ValueError, match="not divisible"):
        arc_margin_ce_sharded(feats, weight, labels, mesh, meshlib.MODEL_AXIS)


def _arcface_cfg(sharded_ce: bool):
    cfg = get_preset("arcface")
    cfg.data.image_size = 32
    cfg.data.num_classes = 16
    cfg.data.batch_size = 8
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.parallel.arcface_sharded_ce = sharded_ce
    return cfg


def test_arcface_sharded_step_matches_dense_step():
    """Full train-step equivalence: the partial-FC step (flag on) and the
    dense step produce the same loss/metrics from identical initial state
    on a data×model mesh."""
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 16, 8).astype(np.int32)

    results = {}
    for name, flag in (("dense", False), ("sharded", True)):
        cfg = _arcface_cfg(flag)
        with mesh:
            model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
            step = make_train_step(cfg, model, tx, mesh=mesh)
            x = jax.device_put(images, meshlib.batch_sharding(mesh))
            y = jax.device_put(labels, meshlib.batch_sharding(mesh))
            state, metrics = step(state, x, y)
            state, metrics = step(state, x, y)  # second step: grads applied
            results[name] = {k: float(v) for k, v in metrics.items()}
    for k in ("loss", "top1", "top3"):
        np.testing.assert_allclose(
            results["sharded"][k], results["dense"][k], atol=1e-4), k


def test_sharded_ce_flag_without_model_axis_raises():
    """--sharded_ce with no model axis must fail loudly, not silently run
    the dense (B, C) path it exists to avoid."""
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    cfg = get_preset("arcface")
    cfg.data.image_size = 32
    cfg.data.num_classes = 16
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.parallel.arcface_sharded_ce = True
    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()), 1))
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        with pytest.raises(ValueError, match="model axis"):
            make_train_step(cfg, model, tx, mesh=mesh)
        with pytest.raises(ValueError, match="model axis"):
            make_train_step(cfg, model, tx)  # no mesh at all


def test_arcface_sharded_eval_matches_dense_eval():
    """Partial-FC eval (m=0 → s·cosθ scores, valid-masked) must produce the
    same loss_sum/top-k counts as the dense eval step, including a
    wrap-padded final batch."""
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_eval_step

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    rng = np.random.default_rng(1)
    images = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 16, 8).astype(np.int32)
    valid = np.array([1, 1, 1, 1, 1, 1, 0, 0], np.float32)  # padded tail

    results = {}
    for name, flag in (("dense", False), ("sharded", True)):
        cfg = _arcface_cfg(flag)
        with mesh:
            model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
            ev = make_eval_step(cfg, model, mesh=mesh)
            x = jax.device_put(images, meshlib.batch_sharding(mesh))
            y = jax.device_put(labels, meshlib.batch_sharding(mesh))
            m = jax.device_put(valid, meshlib.batch_sharding(mesh))
            results[name] = {k: float(v) for k, v in ev(state, x, y, m).items()}
    for k in ("loss_sum", "top1", "top3", "n"):
        np.testing.assert_allclose(
            results["sharded"][k], results["dense"][k], atol=1e-4)
