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
from ..models.factory import build_model
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

    Parameters are initialized on host, placed according to
    `parallel.mesh.param_shardings` (replicated under pure DP; class-dim
    sharded heads under a >1 'model' axis), and the optimizer state is created
    *under jit* so XLA propagates the parameter shardings into the momentum
    tree — no hand-written opt-state sharding rules.
    """
    model = build_model(cfg.model, cfg.data.num_classes, mesh=mesh,
                        pipeline_microbatches=cfg.parallel.pipeline_microbatches)
    if rng is None:
        rng = jax.random.PRNGKey(cfg.run.seed)
    p_rng, d_rng = jax.random.split(rng)

    h = w = cfg.data.image_size
    img = jnp.zeros((2, h, w, 3), jnp.float32)
    if cfg.model.arch == "decoder_lm":
        # token ids; parameters do not depend on T, so a few positions do
        img = jnp.zeros((2, min(cfg.model.decoder.seq_len, 8)), jnp.int32)
    rngs = {"params": p_rng, "dropout": d_rng}
    if cfg.model.head == "arcface":
        variables = model.init(rngs, img, jnp.zeros((2,), jnp.int32), train=False)
    elif cfg.model.head == "nested":
        variables = model.init(rngs, img, None, train=False)
    else:
        variables = model.init(rngs, img, train=False)

    if cfg.model.pretrained:
        if not cfg.model.pretrained_path:
            raise ValueError(
                "model.pretrained=True requires model.pretrained_path: this "
                "environment cannot download torchvision weights (zero "
                "egress); supply a local .pth (torchvision state_dict or "
                "reference NESTED format) via --pretrained_path")
        variables = _load_pretrained(cfg, variables)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    tx = build_optimizer(cfg.optim, steps_per_epoch, freeze_bn=cfg.model.freeze_bn,
                         grad_accum=cfg.parallel.grad_accum)

    params = jax.device_put(params, meshlib.param_shardings(params, mesh))
    batch_stats = jax.device_put(batch_stats, meshlib.replicated(mesh))
    # jit does NOT propagate param shardings into the momentum leaves (they
    # land on one device); re-place them under the explicit rules so the
    # whole state carries NamedShardings — required for restore, where leaves
    # are device_put onto the template's shardings (parallel/mesh.py).
    # Under ZeRO-1 (parallel.zero_opt, default auto=on when the data axis
    # spans devices) each big momentum leaf additionally partitions over
    # 'data' — the step's output constraints (train/steps.py) keep the
    # layout stable, so every state buffer aliases across steps.
    zero = meshlib.zero_opt_enabled(cfg.parallel.zero_opt, mesh)
    opt_state = jax.jit(tx.init)(params)
    opt_state = jax.device_put(
        opt_state, meshlib.opt_shardings(opt_state, mesh, zero_data=zero))

    state = TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), meshlib.replicated(mesh)),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
    )
    return model, tx, state


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
