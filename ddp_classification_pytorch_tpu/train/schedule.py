"""LR schedules + optimizer assembly.

Parity targets (SURVEY C26/C27):
- StepLR(step_size=10, gamma=0.1) — BASELINE/main.py:154, ARCFACE:255
- MultiStepLR(milestones) — CDR/main.py:340, NESTED/train.py:423
- linear per-iteration warmup from 1e-6 to target lr — BASELINE `WarmUp`
  :170-197, NESTED `LrWarmUp` :276-327 (both step the lr every iteration)
- SGD(momentum=0.9) / Adam switch — BASELINE:153, ARCFACE:248-253
- CDR selective-gradient transform chained before SGD (CDR/main.py:179-215)
- NESTED freeze-BN: BN scale/bias receive no updates
  (NESTED/model/model.py:44-55 freezes BN weights by eval()+no-grad)

The reference mutates `optimizer.param_groups[*]['lr']` imperatively; here the
whole schedule is one pure `schedule(step) -> lr` function baked into the
jitted update — no host round-trip per step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax

from ..config import OptimConfig
from ..ops.cdr import cdr_clip_schedule, cdr_gradient_transform


def build_schedule(cfg: OptimConfig, steps_per_epoch: int,
                   grad_accum: int = 1) -> optax.Schedule:
    if cfg.schedule == "step":
        # lr · γ^(epoch // step_size)
        main = optax.exponential_decay(
            cfg.lr, transition_steps=cfg.step_size * steps_per_epoch,
            decay_rate=cfg.gamma, staircase=True,
        )
    elif cfg.schedule == "multistep":
        main = optax.piecewise_constant_schedule(
            cfg.lr,
            {int(m) * steps_per_epoch: cfg.gamma for m in cfg.milestones},
        )
    elif cfg.schedule == "constant":
        main = optax.constant_schedule(cfg.lr)
    else:
        raise ValueError(f"unknown schedule {cfg.schedule!r}")

    # warmup_iters is specified in ITERATIONS (reference NESTED/train.py:466);
    # under accumulation the schedule counts optimizer steps, so rescale
    warmup_iters = max(cfg.warmup_iters // max(grad_accum, 1), 0)
    if warmup_iters > 0:
        # The reference ramps lr per-iteration while the epoch-indexed decay
        # schedule keeps counting from epoch 0 (NESTED/train.py:292-295 with
        # MultiStepLR stepping per epoch at :447-448). optax.join_schedules
        # would shift `main` by warmup_iters — so overlay instead: decay
        # milestones stay anchored at the true global step.
        warm = optax.linear_schedule(cfg.warmup_start_lr, cfg.lr, warmup_iters)

        def overlaid(step):
            return jnp.where(step < warmup_iters, warm(step), main(step))

        return overlaid
    return main


def _is_bn_param(path, _value) -> bool:
    keys = "/".join(str(getattr(k, "key", k)) for k in path).lower()
    return "batchnorm" in keys or "bn_" in keys or keys.endswith("_bn") or "/bn" in keys


def _group_tx(cfg: OptimConfig, schedule) -> optax.GradientTransformation:
    """weight_decay + sgd/adam for ONE param group's hyperparams."""
    if cfg.optimizer == "sgd":
        base = optax.sgd(schedule, momentum=cfg.momentum)
    elif cfg.optimizer == "adam":
        base = optax.adam(schedule, b2=cfg.adam_b2)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.weight_decay:
        return optax.chain(optax.add_decayed_weights(cfg.weight_decay), base)
    return base


# Top-level param-tree keys forming the "head" group when head_lr /
# head_weight_decay diverge a second param group (the reference's optimizer
# group 2 is the ArcMarginProduct module, arc_main.py:248-253; our
# ArcFaceModel names that subtree "margin").
HEAD_GROUP_KEYS = ("margin",)


def build_optimizer(
    cfg: OptimConfig,
    steps_per_epoch: int,
    freeze_bn: bool = False,
    grad_accum: int = 1,
) -> optax.GradientTransformationExtraArgs:
    # The accumulated train step (steps.py `_scan_microbatches`) scans its K
    # microbatches INSIDE one jitted step and applies ONE optimizer update
    # per loader batch — so steps_per_epoch already counts optimizer steps
    # and the schedule needs no rescaling. Only warmup_iters, specified in
    # reference ITERATIONS, rescales (inside build_schedule).
    schedule = build_schedule(cfg, steps_per_epoch, grad_accum=grad_accum)

    if cfg.head_lr is not None or cfg.head_weight_decay is not None:
        # Two param groups in one optimizer (arc_main.py:248-253): the head
        # group (HEAD_GROUP_KEYS subtrees) runs its own lr/weight_decay
        # through the SAME schedule shape; everything else is the base group.
        head_cfg = dataclasses.replace(
            cfg,
            lr=cfg.lr if cfg.head_lr is None else cfg.head_lr,
            weight_decay=(cfg.weight_decay if cfg.head_weight_decay is None
                          else cfg.head_weight_decay),
        )
        head_sched = build_schedule(head_cfg, steps_per_epoch,
                                    grad_accum=grad_accum)

        def label_fn(params):
            if not any(k in HEAD_GROUP_KEYS for k in params):
                # silently training everything at the base hyperparams would
                # hide the misconfiguration (e.g. --head_lr on baseline)
                raise ValueError(
                    f"head_lr/head_weight_decay set but no head param group "
                    f"{HEAD_GROUP_KEYS} in the param tree (top-level keys: "
                    f"{sorted(params)}); these flags apply to the ArcFace "
                    f"margin head")
            return {
                k: jax.tree_util.tree_map(
                    lambda _: "head" if k in HEAD_GROUP_KEYS else "base", v)
                for k, v in params.items()
            }

        base = optax.multi_transform(
            {"base": _group_tx(cfg, schedule),
             "head": _group_tx(head_cfg, head_sched)},
            label_fn)
    else:
        base = _group_tx(cfg, schedule)

    parts = []
    if cfg.grad_transform == "cdr":
        nz = 1.0 - cfg.noise_rate
        if cfg.cdr_dead_schedule:
            # reference's actual behavior: constant clip (CDR/main.py:227)
            parts.append(cdr_gradient_transform(nz, nz))
        else:
            # the intended gradual ramp (CDR/main.py:222-226): clip 1.0 at
            # epoch 0 down to 1-noise_rate by epoch num_gradual, constant
            # after — indexed in-jit off the transform's own step counter
            sched = cdr_clip_schedule(cfg.noise_rate, cfg.num_gradual,
                                      cfg.num_gradual, dead_schedule=False)
            parts.append(cdr_gradient_transform(
                nz, clip_schedule=sched, steps_per_epoch=steps_per_epoch))
    # weight decay lives inside each group's transform (_group_tx)
    parts.append(base)
    if freeze_bn:
        # zero out BN parameter updates (running stats are already frozen by
        # the model's freeze_bn flag)
        parts.append(
            optax.masked(
                optax.set_to_zero(),
                lambda params: jax.tree_util.tree_map_with_path(_is_bn_param, params),
            )
        )
    # No optax.MultiSteps wrapper for grad_accum: accumulation lives in the
    # train step's microbatch scan (steps.py), which hands this transform
    # ONE summed-mean gradient per optimizer step — wrapping would divide
    # the schedule by K a second time (the classic off-by-K accumulation
    # bug the LR-trace test pins).
    return optax.with_extra_args_support(optax.chain(*parts))
