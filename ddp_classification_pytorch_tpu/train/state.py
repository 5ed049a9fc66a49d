"""TrainState: the complete training state as one pure pytree.

The reference's equivalent state is scattered across mutable objects — the
DDP-wrapped `model` (params + BN buffers), `optimizer.state` (momentum), the
`scheduler`, and a Python step counter (BASELINE/main.py:147-154,258-317).
Here it is a single immutable pytree so that:

- the jitted train step is `state -> state` with `donate_argnums=0` (buffers
  reused in place on device — the functional answer to in-place `.step()`);
- checkpointing is `serialize(state)` — no `state_dict()` protocols;
- sharding is a pytree-of-`NamedSharding` matching this tree.

`apply_fn`/`tx` are deliberately NOT stored in the pytree (unlike
`flax.training.TrainState`): they are static Python closures held by the step
builder, keeping this tree 100% arrays — trivially shardable/serializable.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..config import Config
from ..models.factory import build_model, model_report
from ..parallel import mesh as meshlib
from .schedule import build_optimizer


class TrainState(struct.PyTreeNode):
    step: jax.Array            # global step counter (drives schedules/rng)
    params: Any                # model parameters (f32)
    batch_stats: Any           # BatchNorm running statistics (f32)
    opt_state: Any             # optax state (momentum etc.)


def create_train_state(
    cfg: Config,
    mesh: Any,
    steps_per_epoch: int,
    rng: Optional[jax.Array] = None,
):
    """Build (model, tx, sharded TrainState) for a workload config.

    The state is the output of ONE jitted program: the initialisers' draws
    from `rng`, the optimizer's zeros and step 0, each leaf born in its
    final `NamedSharding` (`state_shardings`, read from the program's
    output shapes before anything runs). Nothing here computes on the
    device op by op: an eager `model.init` is a few hundred one-op
    compiles at every process start, none of them long enough for the
    persistent cache to keep. XLA drops the dummy forward that `init`
    traces, since no output depends on it.

    `--pretrained_path` makes it two programs with a host step between:
    the variables, the numpy/torch overlay, then the state around them.
    """
    if cfg.model.pretrained and not cfg.model.pretrained_path:
        raise ValueError(
            "model.pretrained=True requires model.pretrained_path: this "
            "environment cannot download torchvision weights (zero "
            "egress); supply a local .pth (torchvision state_dict or "
            "reference NESTED format) via --pretrained_path")
    model = build_model(cfg.model, cfg.data.num_classes, mesh=mesh,
                        pipeline_microbatches=cfg.parallel.pipeline_microbatches)
    tx = build_optimizer(cfg.optim, steps_per_epoch, freeze_bn=cfg.model.freeze_bn,
                         grad_accum=cfg.parallel.grad_accum)
    if rng is None:
        rng = jax.random.PRNGKey(cfg.run.seed)

    def init_variables(rng):
        p_rng, d_rng = jax.random.split(rng)
        inputs = [model_report(cfg.model).init_inputs(cfg.data.image_size)]
        if cfg.model.head == "arcface":
            inputs.append(jnp.zeros((2,), jnp.int32))  # labels
        elif cfg.model.head == "nested":
            inputs.append(None)
        return model.init({"params": p_rng, "dropout": d_rng}, *inputs, train=False)

    def state_around(variables):
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=variables["params"],
            batch_stats=variables.get("batch_stats", {}),
            opt_state=tx.init(variables["params"]),
        )

    # Under ZeRO-1 (parallel.zero_opt, default auto=on when the data axis
    # spans devices) each big momentum leaf additionally partitions over
    # 'data' — the step's output constraints (train/steps.py) keep the
    # layout stable, so every state buffer aliases across steps.
    zero = meshlib.zero_opt_enabled(cfg.parallel.zero_opt, mesh)

    def born_sharded(make, arg):
        shapes = jax.eval_shape(make, arg)
        return jax.jit(make, out_shardings=state_shardings(shapes, mesh, zero))(arg)

    if cfg.model.pretrained:
        variables = _load_pretrained(cfg, jax.jit(init_variables)(rng))
        state = born_sharded(state_around, variables)
    else:
        state = born_sharded(lambda r: state_around(init_variables(r)), rng)
    return model, tx, state


def state_shardings(state: TrainState, mesh: Any, zero: bool) -> TrainState:
    """The declared layout: the `NamedSharding` of every leaf of a TrainState
    (arrays, avals or tracers: only shape and dtype are read). Parameters by
    `param_shardings` (replicated under pure DP; class-dim sharded heads
    under a >1 'model' axis), batch stats and step replicated, the optimizer
    state by `opt_shardings` (ZeRO-1's data-axis shards when `zero`). The
    initial state is born in it, the train step pins its output to it, and
    restore places onto it (parallel/mesh.py)."""
    rep = meshlib.replicated(mesh)
    return TrainState(
        step=rep,
        params=meshlib.param_shardings(state.params, mesh),
        batch_stats=jax.tree_util.tree_map(lambda _: rep, state.batch_stats),
        opt_state=meshlib.opt_shardings(state.opt_state, mesh, zero_data=zero),
    )


def _load_pretrained(cfg: Config, variables):
    """Overlay converted torch weights onto the backbone subtree, choosing
    the converter by arch (reference `pretrained=True` defaults:
    torchvision ResNets BASELINE/main.py:135 / NESTED
    imagenet_resnet.py:195-203; torchvision vgg19_bn NESTED/model/vgg.py:13-17;
    timm tresnet_m_miil_in21k BASELINE/main.py:141-144)."""
    from ..models import import_torch as it

    sd = it.load_torch_checkpoint(cfg.model.pretrained_path)
    backbone_params = variables["params"]["backbone"]
    # (converter, flax head module, torch head key) per arch family; the
    # torchvision/timm fc imports only when the model keeps a same-width
    # head (the reference always replaces it: 1000 → NUM_CLASS,
    # BASELINE:136-139; for VGG the replaceable head is fc3)
    converter, flax_fc, torch_fc = {
        "vgg19_bn": (it.convert_vgg_state_dict, "fc3", "classifier.6.weight"),
        "tresnet_m": (it.convert_tresnet_state_dict, "fc", "head.fc.weight"),
        "timm": (it.convert_tresnet_state_dict, "fc", "head.fc.weight"),
    }.get(cfg.model.arch,
          (it.convert_resnet_state_dict, "fc", "fc.weight"))
    fc_kernel = backbone_params.get(flax_fc, {}).get("kernel")
    w = sd.get(torch_fc)
    include_fc = (fc_kernel is not None and w is not None
                  and tuple(fc_kernel.shape) == tuple(reversed(w.shape)))
    converted = converter(sd, include_fc=include_fc)
    sub = {
        "params": variables["params"]["backbone"],
        "batch_stats": variables.get("batch_stats", {}).get("backbone", {}),
    }
    merged = it.merge_into_variables(sub, converted)
    out_params = dict(variables["params"])
    out_params["backbone"] = merged["params"]
    out = dict(variables)
    out["params"] = out_params
    if "batch_stats" in variables and merged.get("batch_stats"):
        out_stats = dict(variables["batch_stats"])
        out_stats["backbone"] = merged["batch_stats"]
        out["batch_stats"] = out_stats
    return out


def param_count(state: TrainState) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(state.params))
