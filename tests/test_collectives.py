"""Explicit shard_map DDP step vs the auto-sharded jit step: same math.

Runs both on the 8-device CPU mesh from identical initial state and batch;
parameters after one step must agree to float tolerance (reduction order may
differ), proving the auto-sharded path really does compute DDP semantics.

The explicit step lives here, not in the package (no command runs it): the
closest structural analogue of the reference's DDP backend (SURVEY §2.3) and
an executable specification of what the framework's data parallelism does:

- per-device shard computes grads on ITS batch shard          (DDP backward)
- `jax.lax.pmean(grads, 'data')`                               (NCCL allreduce)
- BatchNorm with `axis_name='data'` pmeans the batch stats     (SyncBatchNorm)
- metrics `psum` over the axis                                 (dist.reduce, exact)
"""

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from ddp_classification_pytorch_tpu.config import Config, get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.parallel.collectives import build_ddp_model
from ddp_classification_pytorch_tpu.parallel.mesh import DATA_AXIS
from ddp_classification_pytorch_tpu.train.schedule import build_optimizer
from ddp_classification_pytorch_tpu.train.state import (TrainState,
                                                        create_train_state)
from ddp_classification_pytorch_tpu.train.steps import make_train_step
from ddp_classification_pytorch_tpu.utils.compat import shard_map_unchecked
from ddp_classification_pytorch_tpu.utils.metrics import topk_hits


def make_shard_map_train_step(
    cfg: Config,
    model: Any,
    tx: optax.GradientTransformationExtraArgs,
    mesh: Any,
    base_rng: Optional[jax.Array] = None,
) -> Callable[[TrainState, jnp.ndarray, jnp.ndarray], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Jitted `(state, images, labels) -> (state, metrics)` with explicit
    per-shard grads + pmean sync. Supports the plain-classifier workloads
    (baseline/cdr); margin/nested heads use the auto-sharded path."""
    if base_rng is None:
        base_rng = jax.random.PRNGKey(cfg.run.seed + 1)

    def per_shard(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray):
        def loss_fn(params, batch_stats):
            variables = {"params": params, "batch_stats": batch_stats}
            # fold in the shard index too: each data shard must draw its own
            # dropout masks (the auto-sharded path's global batch does)
            rng = jax.random.fold_in(
                jax.random.fold_in(base_rng, state.step),
                jax.lax.axis_index(DATA_AXIS))
            logits, mutated = model.apply(
                variables, images, train=True, mutable=["batch_stats"],
                rngs={"dropout": rng})
            # local mean; the grad pmean below makes the global mean exact
            # because every shard holds the same number of samples
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels).mean()
            return loss, (mutated.get("batch_stats", batch_stats), logits)

        (loss, (new_stats, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, state.batch_stats)
        # THE collective: DDP's bucketed allreduce in one line
        grads = jax.lax.pmean(grads, DATA_AXIS)
        loss = jax.lax.pmean(loss, DATA_AXIS)
        # BN stats were already pmean'd inside BatchNorm via axis_name; they
        # are identical across shards — no further sync needed
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)

        n_local = labels.shape[0]
        top1 = jax.lax.psum(topk_hits(logits, labels, 1).sum(), DATA_AXIS)
        top3 = jax.lax.psum(topk_hits(logits, labels, 3).sum(), DATA_AXIS)
        n = jax.lax.psum(jnp.asarray(n_local, jnp.float32), DATA_AXIS)
        metrics = {"loss": loss, "top1": top1 / n, "top3": top3 / n}
        new_state = state.replace(
            step=state.step + 1, params=new_params,
            batch_stats=new_stats, opt_state=new_opt)
        return new_state, metrics

    # replication checking can't prove the in-shard optimizer update is
    # replicated (it is, by construction: pmean'd grads), so it is off
    sharded = shard_map_unchecked(
        per_shard, mesh=mesh, in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P()))
    return jax.jit(sharded, donate_argnums=0)


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
