"""Per-block rematerialization must be numerically transparent."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddp_classification_pytorch_tpu.models import resnet as R


def test_remat_gradients_match():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, 4), jnp.int32)

    def grads_for(remat):
        # one BasicBlock a stage: remat wraps each block alike, so four show
        # what eight would
        model = R.ResNet(stage_sizes=(1, 1, 1, 1), block_cls=R.BasicBlock,
                         num_classes=4, cifar_stem=True, dtype=jnp.float32,
                         remat=remat)
        variables = jax.jit(lambda: model.init(jax.random.PRNGKey(0), x, train=False))()

        def loss(params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        # jitted, as the train step holds it: one program, not an op at a time
        return jax.jit(jax.grad(loss))(variables["params"])

    g0 = grads_for(False)
    g1 = grads_for(True)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)
