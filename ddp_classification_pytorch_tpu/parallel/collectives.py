"""Explicit-collective training step via shard_map.

The default train path (`train/steps.py`) lets XLA derive every collective
from shardings. This module is the explicit counterpart — the closest
structural analogue of the reference's DDP backend (SURVEY §2.3), useful when
the automatic partitioner needs overriding and as an executable specification
of what the framework's data parallelism does:

- per-device shard computes grads on ITS batch shard          (DDP backward)
- `jax.lax.pmean(grads, 'data')`                               (NCCL allreduce)
- BatchNorm with `axis_name='data'` pmeans the batch stats     (SyncBatchNorm)
- metrics `psum` over the axis                                 (dist.reduce, exact)

Numerically this matches the auto-sharded path up to floating-point reduction
order (test_collectives.py asserts closeness).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from typing import TYPE_CHECKING

from ..utils.compat import shard_map_unchecked

from ..config import Config
from ..models.factory import build_model
from ..utils.metrics import topk_hits
from .mesh import DATA_AXIS

if TYPE_CHECKING:  # runtime import would be circular (train.state → parallel)
    from ..train.state import TrainState


def build_ddp_model(cfg: Config):
    """Model whose BatchNorm carries the 'data' axis name (explicit SyncBN)."""
    return build_model(cfg.model, cfg.data.num_classes, axis_name=DATA_AXIS)


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
    # donate_argnums=0 is audited (analysis/jaxpr_audit.py): every state
    # byte must alias in the executable — this entry also opts INTO the
    # collectives check exemption, since explicit psum/pmean IS its point
    return jax.jit(sharded, donate_argnums=0)
