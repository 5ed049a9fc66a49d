"""Each plain reference against the program's model at tiny widths on the
CPU, in float32 on seeded weights: loss and per-leaf gradient norms to
1e-5 (which proves the key-path mapping), three optimizer steps through
the program's own train step (which proves the optimizers and the
flip-mask derivation), and the direction of the tolerance: a bf16 run
differs from the reference by more than a float32 run does, an fp8 run
(the control) by more again."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import common, resnet, vit
from benchmark.runners import train as runner

RESNET = {"block": "bottleneck", "stage_sizes": [1, 1, 1, 1], "num_filters": 8,
          "stem": "imagenet", "num_classes": 10}
RESNET_BASIC = {"block": "basic", "stage_sizes": [1, 1], "num_filters": 8,
                "stem": "cifar", "num_classes": 10}
VIT = {"patch": 4, "dim": 32, "depth": 2, "heads": 2, "image_size": 16,
       "num_classes": 10}


def program_model(kind, arch, dtype):
    from ddp_classification_pytorch_tpu.models.factory import ClassifierModel
    from ddp_classification_pytorch_tpu.models.resnet import BasicBlock, Bottleneck, ResNet
    from ddp_classification_pytorch_tpu.models.vit import ViT

    if kind == "resnet":
        block = Bottleneck if arch["block"] == "bottleneck" else BasicBlock
        return ClassifierModel(ResNet(
            stage_sizes=tuple(arch["stage_sizes"]), block_cls=block,
            num_classes=arch["num_classes"], num_filters=arch["num_filters"],
            cifar_stem=arch["stem"] == "cifar", dtype=dtype))
    return ClassifierModel(ViT(patch=arch["patch"], dim=arch["dim"],
                               depth=arch["depth"], heads=arch["heads"],
                               num_classes=arch["num_classes"], dtype=dtype))


def program_loss_and_grads(kind, arch, dtype, flat, x, y):
    """The program's model on the reference's weights, unflattened by key path."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    model = program_model(kind, arch, dtype)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
    have = {"/".join(k): v.shape for k, v in flatten_dict(variables["params"]).items()}
    assert have == {k: v.shape for k, v in flat.items()}
    params = unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables.get("batch_stats", {})}, x,
            train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y).mean()

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
    norms = {"/".join(k): float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
             for k, v in flatten_dict(grads).items()}
    return float(loss), norms


CASES = [("resnet", resnet, RESNET, 32), ("resnet", resnet, RESNET_BASIC, 16),
         ("vit", vit, VIT, 16)]


@pytest.mark.parametrize("kind,ref,arch,size", CASES,
                         ids=["resnet_bottleneck", "resnet_basic", "vit"])
def test_reference_agrees_with_the_program_in_float32(kind, ref, arch, size):
    flat = common.make_params(ref.param_spec(arch), 7)
    # batch norm scales of 1 and zero biases hide a swapped leaf: perturb all
    flat = {k: v + 0.05 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
            for i, (k, v) in enumerate(sorted(flat.items()))}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, size, size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = common.loss_and_grad(ref.forward_for(arch), flat, x, y)
        blocked = common.loss_and_grad(ref.forward_for(arch), flat, x, y, 4)[0] \
            if kind == "vit" else loss
    got_loss, got = program_loss_and_grads(kind, arch, jnp.float32, flat, x, y)
    assert abs(got_loss - float(loss)) < 1e-5 * abs(float(loss))
    assert abs(float(blocked) - float(loss)) < 1e-5 * abs(float(loss))
    ref_norms = {k: float(v) for k, v in common.leaf_norms(grads).items()}
    gap, leaf = common.worst_leaf_gap(got, ref_norms)
    assert gap < 1e-4, (gap, leaf)


@pytest.mark.parametrize("kind,ref,arch,size", CASES[::2], ids=["resnet", "vit"])
def test_lower_precision_is_further_from_the_reference(kind, ref, arch, size):
    flat = common.make_params(ref.param_spec(arch), 11)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, size, size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref_norms = {k: float(v) for k, v in common.leaf_norms(
            common.loss_and_grad(ref.forward_for(arch), flat, x, y)[1]).items()}
        gaps = {}
        for precision in ("bfloat16", "fp8"):
            g = common.loss_and_grad(ref.forward_for(arch, precision), flat, x, y)[1]
            gaps[precision] = common.worst_leaf_gap(
                {k: float(v) for k, v in common.leaf_norms(g).items()}, ref_norms)[0]
    f32 = common.worst_leaf_gap(
        program_loss_and_grads(kind, arch, jnp.float32, flat, x, y)[1], ref_norms)[0]
    bf16 = common.worst_leaf_gap(
        program_loss_and_grads(kind, arch, jnp.bfloat16, flat, x, y)[1], ref_norms)[0]
    assert f32 < 1e-4 < bf16
    assert gaps["bfloat16"] > 10 * f32
    assert gaps["fp8"] > 1.5 * gaps["bfloat16"]


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1e-9, "c": 2.0}
    got = {"a": 1.1, "b": 3e-9, "c": 2.0}
    gap, leaf = common.worst_leaf_gap(got, ref)
    assert leaf == "a" and abs(gap - 0.1) < 1e-12  # b's tiny norm is held to the median
    with pytest.raises(ValueError):
        common.worst_leaf_gap({"a": 1.0}, ref)
    assert common.worst_leaf_gap({**got, "a": float("nan")}, ref)[0] == float("inf")


@pytest.mark.parametrize("kind", ["sgd", "adam"])
def test_three_steps_follow_the_programs_own_train_step(kind):
    """Float32 program step (image-folder config: uint8 wire, device flip)
    against the reference trajectory: optimizer arithmetic, first-moment
    read-back and the flip mask all have to be right for 1e-4."""
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    arch = {"block": "basic", "stage_sizes": [2, 2, 2, 2], "num_filters": 64,
            "stem": "cifar", "num_classes": 10}
    opt = ({"kind": "sgd", "lr": 0.01, "momentum": 0.9} if kind == "sgd"
           else {"kind": "adam", "lr": 0.001})
    cfg = get_preset("baseline")
    cfg.model.arch, cfg.model.variant, cfg.model.dtype = "resnet18", "cifar", "float32"
    cfg.data.dataset, cfg.data.input_dtype = "imagefolder", "uint8"
    cfg.data.image_size, cfg.data.num_classes, cfg.data.batch_size = 16, 10, 8
    cfg.optim.optimizer, cfg.optim.lr = kind, opt["lr"]
    cfg.run.seed = 5
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1, 1), devices=jax.devices()[:1])
    model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=100)
    step = make_train_step(cfg, model, tx, mesh=mesh)

    spec = resnet.param_spec(arch)
    flat = common.make_params(spec, 5)
    names, leaves, treedef = runner.leaf_names(state.params)
    assert {n: x.shape for n, x in zip(names, leaves)} == {n: s[0] for n, s in spec.items()}
    state = state.replace(params=jax.tree_util.tree_unflatten(
        treedef, [jnp.array(flat[n]) for n in names]))
    rng = np.random.default_rng(2)
    images = rng.integers(0, 256, (3, 8, 16, 16, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (3, 8)).astype(np.int32)
    losses, grad0 = [], None
    with jax.default_matmul_precision("highest"):
        for s in range(3):
            state, metrics = step(state, jnp.asarray(images[s]), jnp.asarray(labels[s]))
            losses.append(float(metrics["loss"]))
            if s == 0:
                moment, factor = runner.first_moment(state.opt_state, opt)
                moment = jax.tree_util.tree_map(jnp.copy, moment)  # step 1 donates
                grad0 = {n: factor * float(jnp.linalg.norm(x.ravel())) for n, x in
                         zip(names, jax.tree_util.tree_leaves(moment))}
    dparam = {n: float(jnp.linalg.norm((x - flat[n]).ravel())) for n, x in
              zip(names, jax.tree_util.tree_leaves(state.params))}

    ref_step = common.make_step(resnet.forward_for(arch), opt)
    out = common.trajectory(ref_step, opt, flat, jnp.asarray(images),
                            jnp.asarray(labels), runner.flip_masks(5, 3, 8))
    for a, b in zip(losses, out["loss"]):
        assert abs(a - float(b)) < 1e-4 * abs(float(b))
    assert common.worst_leaf_gap(grad0, {k: float(v) for k, v in out["grad0_norms"].items()})[0] < 1e-3
    # the gradient itself, element by element, as `grad0_diff_gap` reads it
    got = {n: factor * x for n, x in zip(names, jax.tree_util.tree_leaves(moment))}
    assert common.difference_gap(got, out["grad0"]) < 1e-3
    assert common.worst_leaf_gap(dparam, {k: float(v) for k, v in out["dparam"].items()})[0] < 1e-3
    # without the flip the same comparison fails: the mask is load-bearing
    noflip = common.trajectory(ref_step, opt, flat, jnp.asarray(images), jnp.asarray(labels))
    assert abs(losses[0] - float(noflip["loss"][0])) > 1e-4 * losses[0]
