"""TResNet-M: shapes, train/eval modes, stats updates, and a train step."""

import jax
import jax.numpy as jnp
import numpy as np

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.models.tresnet import space_to_depth, tresnet_m


def test_space_to_depth_roundtrip():
    x = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
    y = space_to_depth(x, 4)
    assert y.shape == (2, 2, 2, 48)
    # every input element survives exactly once
    np.testing.assert_array_equal(
        np.sort(np.asarray(y).ravel()), np.sort(np.asarray(x).ravel())
    )


def test_tresnet_forward_shapes_and_stats():
    model = tresnet_m(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)

    def init_then_train_pass():
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        return variables, model.apply(variables, x, train=True,
                                      mutable=["batch_stats"])

    # one jitted program, not a few hundred ops compiled one by one
    variables, (logits, mutated) = jax.jit(init_then_train_pass)()
    assert "batch_stats" in variables
    assert logits.shape == (2, 10)

    # train-mode pass must update the running stats
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(before, after))

    # of the eval pass only the shape is asserted: a trace gives it
    eval_logits = jax.eval_shape(lambda v: model.apply(v, x, train=False),
                                 variables)
    assert eval_logits.shape == (2, 10)


def test_tresnet_feature_mode():
    model = tresnet_m(num_classes=0, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    # shapes are what is asserted: a trace gives them, nothing runs
    feats = jax.eval_shape(lambda: model.apply(
        model.init(jax.random.PRNGKey(0), x, train=False), x, train=False))
    assert feats.shape == (2, 2048)  # stage-4 bottleneck: 512 · expansion 4


def test_tresnet_train_step_runs():
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    cfg = get_preset("baseline")
    cfg.model.arch = "tresnet_m"
    cfg.model.dtype = "float32"
    cfg.data.image_size = 64
    cfg.data.num_classes = 4
    cfg.data.batch_size = 16

    # two devices: the step's program is the same on eight, and every device
    # more draws and updates all 29 M parameters again
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 1), jax.devices()[:2])
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        step = make_train_step(cfg, model, tx)
        rng = np.random.default_rng(0)
        images = jax.device_put(
            rng.normal(size=(16, 64, 64, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh))
        labels = jax.device_put(
            rng.integers(0, 4, 16).astype(np.int32), meshlib.batch_sharding(mesh))
        state, metrics = step(state, images, labels)
        assert np.isfinite(float(metrics["loss"]))


def test_tresnet_odd_stage_dims_forward():
    """image_size ≡ 4 (mod 8) makes stride-2 stage inputs odd: the ceil-mode
    shortcut avg-pool must match BlurPool's padded output (regression: VALID
    avg-pool floored the shortcut to a smaller map and the residual add
    crashed)."""
    import jax
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.models.tresnet import tresnet_m

    model = tresnet_m(num_classes=3, dtype=jnp.float32)
    # the crash was a shape error at trace time: a trace is the whole test
    out = jax.eval_shape(lambda: model.apply(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 36, 36, 3)), train=False),
        jnp.zeros((2, 36, 36, 3)), train=False))
    assert out.shape == (2, 3)
