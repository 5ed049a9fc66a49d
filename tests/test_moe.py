"""Expert-parallel MoE FFN (ops/moe.py) — exactness on the 8-device mesh.

EP is absent from the reference (SURVEY §2.2); these tests pin the
framework's extension: the expert-sharded path must equal the unsharded
mixture bit-for-bit in values AND gradients, and the ViT-MoE model must
train end-to-end on a data×model mesh with expert banks actually sharded.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.ops.moe import (
    moe_mlp,
    router_logits,
    topk_gates,
)
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib


def _params(c=16, e=4, h=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
    return dict(router_w=mk(c, e), w_in=mk(e, c, h), b_in=mk(e, h),
                w_out=mk(e, h, c), b_out=mk(e, c))


def test_topk_gates_sparse_and_normalized():
    p = _params()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 16)), jnp.float32)
    g = topk_gates(router_logits(x, p["router_w"]), top_k=2)
    nz = np.count_nonzero(np.asarray(g), axis=-1)
    assert (nz == 2).all()
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)


@pytest.mark.parametrize("mp", [2, 4])
def test_moe_sharded_matches_unsharded(mp):
    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()) // mp, mp))
    p = _params()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 8, 16)), jnp.float32)
    gates = topk_gates(router_logits(x, p["router_w"]), top_k=2)
    ew = {k: v for k, v in p.items() if k != "router_w"}
    dense = moe_mlp(x, gates, **ew, dtype=jnp.float32)
    sharded = jax.jit(lambda x, g: moe_mlp(
        x, g, **ew, dtype=jnp.float32, mesh=mesh,
        axis=meshlib.MODEL_AXIS, batch_axis=meshlib.DATA_AXIS))(x, gates)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense), atol=1e-5)


def test_moe_sharded_gradients_match_unsharded():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    p = _params()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(8, 8, 16)), jnp.float32)

    def loss(kind):
        kw = (dict(mesh=mesh, axis=meshlib.MODEL_AXIS,
                   batch_axis=meshlib.DATA_AXIS) if kind == "sharded" else {})

        def f(x, p):
            gates = topk_gates(router_logits(x, p["router_w"]), top_k=2)
            ew = {k: v for k, v in p.items() if k != "router_w"}
            return (moe_mlp(x, gates, **ew, dtype=jnp.float32, **kw) ** 2).mean()

        return f

    gs = jax.jit(jax.grad(loss("sharded"), argnums=(0, 1)))(x, p)
    gd = jax.grad(loss("dense"), argnums=(0, 1))(x, p)
    for a, b in zip(jax.tree_util.tree_leaves(gs), jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_rejects_indivisible_experts():
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    p = _params(e=6, h=8)
    x = jnp.zeros((4, 4, 16), jnp.float32)
    gates = topk_gates(router_logits(x, p["router_w"]), top_k=2)
    ew = {k: v for k, v in p.items() if k != "router_w"}
    with pytest.raises(ValueError, match="not divisible"):
        moe_mlp(x, gates, **ew, mesh=mesh, axis=meshlib.MODEL_AXIS)


def test_vit_moe_trains_on_expert_parallel_mesh():
    """Full dp×ep train step: loss decreases, expert banks sharded over the
    model axis, router replicated."""
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 2))
    cfg = get_preset("baseline")
    cfg.model.arch = "vit_t16_d4"
    cfg.model.dtype = "float32"
    cfg.model.moe_experts = 4
    cfg.data.image_size = 32
    cfg.data.num_classes = 8
    cfg.data.batch_size = 16
    cfg.parallel.model_axis = 2

    rng = np.random.default_rng(0)
    images = rng.normal(size=(16, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 8, 16).astype(np.int32)
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        w = state.params["backbone"]["block0"]["moe_w_in"]
        assert w.sharding.spec[0] == meshlib.MODEL_AXIS, w.sharding
        r = state.params["backbone"]["block0"]["moe_router"]
        assert all(s is None for s in r.sharding.spec), r.sharding

        step = make_train_step(cfg, model, tx, mesh=mesh)
        x = jax.device_put(images, meshlib.batch_sharding(mesh))
        y = jax.device_put(labels, meshlib.batch_sharding(mesh))
        losses = []
        for _ in range(4):
            state, metrics = step(state, x, y)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_invalid_configs_fail_loudly():
    """top_k out of range, non-dividing expert count, and the PP/MoE
    model-axis conflict must all raise instead of silently degrading."""
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.models.factory import build_model

    p = _params(e=2, h=32)
    x = jnp.zeros((2, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="top_k"):
        topk_gates(router_logits(x, p["router_w"]), top_k=3)

    cfg = get_preset("baseline").model
    cfg.arch = "vit_t16_d4"
    cfg.moe_experts = 5  # does not divide 4*192
    model = build_model(cfg, 8)
    with pytest.raises(ValueError, match="divide"):
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((2, 32, 32, 3), jnp.float32), train=False)

    cfg.moe_experts = 4
    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 2))
    with pytest.raises(ValueError, match="one role per config"):
        build_model(cfg, 8, mesh=mesh, pipeline_microbatches=2)


def test_load_balance_loss_penalizes_collapse():
    """A router collapsed onto one expert must score higher than a
    near-uniform one; the uniform limit is ≈ top_k (Switch convention)."""
    from ddp_classification_pytorch_tpu.ops.moe import load_balance_loss

    rng = np.random.default_rng(0)
    # feature 0 strictly positive so a router keyed on it collapses every
    # token onto expert 0 (the router is linear in x — no bias term)
    x = jnp.asarray(np.abs(rng.normal(size=(2, 16, 8))) + 0.1, jnp.float32)
    uniform = jnp.zeros((8, 4), jnp.float32)      # logits all equal
    collapsed = jnp.zeros((8, 4), jnp.float32).at[0, 0].set(50.0)
    lu = float(load_balance_loss(router_logits(x, uniform), top_k=2))
    lc = float(load_balance_loss(router_logits(x, collapsed), top_k=2))
    assert lc > lu
    assert lc == pytest.approx(4.0, abs=0.1)      # E·f_0·p_0 = 4·1·1
    assert lu == pytest.approx(2.0, abs=0.3)      # ≈ top_k when uniform


def test_moe_aux_loss_enters_training_loss():
    """The sown per-block penalties must reach the train loss: weight 0 vs
    default weight give different losses from identical state; and the
    remat path must tolerate the 'losses' collection."""
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    mesh = meshlib.make_mesh(meshlib.MeshSpec(len(jax.devices()), 1))
    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 8, 8).astype(np.int32)
    losses = {}
    for w in (0.0, 0.01):
        cfg = get_preset("baseline")
        cfg.model.arch = "vit_t16_d4"
        cfg.model.dtype = "float32"
        cfg.model.moe_experts = 4
        cfg.model.moe_aux_weight = w
        cfg.model.remat = True
        cfg.data.image_size = 32
        cfg.data.num_classes = 8
        cfg.data.batch_size = 8
        with mesh:
            model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
            step = make_train_step(cfg, model, tx, mesh=mesh)
            x = jax.device_put(images, meshlib.batch_sharding(mesh))
            y = jax.device_put(labels, meshlib.batch_sharding(mesh))
            _, metrics = step(state, x, y)
            losses[w] = float(metrics["loss"])
    assert losses[0.01] > losses[0.0]
    # aux ≈ top_k (2) per block × 4 blocks (vit_t16_d4) × 0.01 weight at init
    assert losses[0.01] - losses[0.0] == pytest.approx(0.08, abs=0.03)
