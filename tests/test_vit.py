"""ViT backbone family — shapes, heads, and sequence-parallel training.

The multi-device tests run the FULL train step with the token axis ring-
sharded over the mesh 'model' axis (shard_map + ppermute inside the jitted
step) on the 8-device CPU mesh — the framework's long-context path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.models.factory import build_model, feat_dim_for
from ddp_classification_pytorch_tpu.models.vit import build_vit
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_eval_step, make_train_step


def _vit_cfg(head="fc", mp=1):
    cfg = get_preset("baseline")
    cfg.model.arch = "vit_t16_d4"
    cfg.model.dtype = "float32"
    cfg.model.head = head
    cfg.data.image_size = 64  # (64/16)² = 16 tokens; divisible by mp ≤ 8
    cfg.data.num_classes = 12
    cfg.data.batch_size = 8
    cfg.parallel.model_axis = mp
    return cfg


def test_vit_feature_and_logit_shapes():
    model = build_vit("vit_t16_d4", num_classes=0, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    vs = model.init(jax.random.PRNGKey(0), x, train=False)
    feats = model.apply(vs, x, train=False)
    assert feats.shape == (2, 192)
    clf = build_vit("vit_t16_d4", num_classes=7, dtype=jnp.float32)
    vs = clf.init(jax.random.PRNGKey(0), x, train=False)
    assert clf.apply(vs, x, train=False).shape == (2, 7)


def test_vit_feat_dim_registry():
    cfg = _vit_cfg()
    assert feat_dim_for(cfg.model) == 192


def _three_losses(mp):
    """Three steps on one fixed batch over data x model = (8 / mp) x mp, the
    step built as the Trainer builds it (`mesh=`: its output shardings are
    the state's own)."""
    cfg = _vit_cfg(mp=mp)
    mesh = meshlib.make_mesh(
        meshlib.MeshSpec(len(jax.devices()) // mp, mp))
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        rng = np.random.default_rng(0)
        images = jax.device_put(
            rng.normal(size=(8, 64, 64, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh))
        labels = jax.device_put(
            rng.integers(0, 12, 8).astype(np.int32),
            meshlib.batch_sharding(mesh))
        losses = []
        for _ in range(3):
            state, metrics = step(state, images, labels)
            losses.append(float(metrics["loss"]))
    return losses


@pytest.fixture(scope="module")
def data_parallel_losses():
    return _three_losses(1)


@pytest.mark.parametrize("mp", [2, 4])
def test_vit_train_step_sequence_parallel(mp, data_parallel_losses):
    """Full jitted train step with dp×sp mesh; loss finite and decreasing-ish,
    and the same as with no ring at all: the ring moves tokens, not results.
    (Without `mesh=` the compiler picks the returned state's shardings, and
    on data=2 x model=4 the program compiled for THAT state reads 2.477 for
    the second loss where every other mesh reads 2.308: PERF.md section 7.)"""
    losses = _three_losses(mp)
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # memorizes a fixed batch within 3 steps
    np.testing.assert_allclose(losses, data_parallel_losses, rtol=1e-4)


def test_vit_sequence_parallel_matches_single_device():
    """Ring-sharded forward == dense forward on identical params."""
    cfg = _vit_cfg(mp=4)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4))
    dense_model = build_model(cfg.model, cfg.data.num_classes)      # no mesh
    ring_model = build_model(cfg.model, cfg.data.num_classes, mesh=mesh)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64, 64, 3)),
                    jnp.float32)
    vs = dense_model.init(jax.random.PRNGKey(0), x, train=False)
    dense = dense_model.apply(vs, x, train=False)
    with mesh:
        ring = ring_model.apply(vs, x, train=False)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=2e-4)


def test_vit_arcface_head_composes():
    """ViT backbone under the ArcFace margin head trains one step."""
    cfg = _vit_cfg(head="arcface", mp=2)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 2))
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        step = make_train_step(cfg, model, tx)
        images = jax.device_put(jnp.ones((8, 64, 64, 3)),
                                meshlib.batch_sharding(mesh))
        labels = jax.device_put(jnp.arange(8, dtype=jnp.int32) % 12,
                                meshlib.batch_sharding(mesh))
        state, metrics = step(state, images, labels)
        assert np.isfinite(float(metrics["loss"]))
        eval_step = make_eval_step(cfg, model)
        out = eval_step(state, images, labels, jnp.ones((8,)))
        assert np.isfinite(float(out["loss_sum"]))


def test_flash_min_tokens_autopick(monkeypatch):
    """Below the flash_min_tokens floor, --flash_attention must route the
    unsharded path to dense attention (measured: dense is equal-or-better
    in the hundreds of tokens, docs/performance.md knob #4); at/above the
    floor — and always when floor=0 — the Pallas kernel runs."""
    import importlib

    attn_mod = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.attention")

    calls = []
    real = attn_mod.ring_attention

    def spy(q, k, v, **kw):
        calls.append(kw.get("use_flash", False))
        return real(q, k, v, **kw)

    monkeypatch.setattr("ddp_classification_pytorch_tpu.models.vit.ring_attention", spy)

    x = jnp.zeros((2, 64, 64, 3))  # 16 tokens
    for floor, expect_flash in [(1024, False), (0, True), (16, True)]:
        calls.clear()
        model = build_vit("vit_t16_d4", num_classes=0, dtype=jnp.float32,
                          use_flash=True, flash_min_tokens=floor)
        vs = model.init(jax.random.PRNGKey(0), x, train=False)
        model.apply(vs, x, train=False)
        assert calls and all(c == expect_flash for c in calls), (floor, calls)


def test_flash_min_tokens_config_plumbs_to_model():
    from ddp_classification_pytorch_tpu.models.factory import build_backbone

    cfg = get_preset("baseline")
    cfg.model.arch = "vit_t16_d4"
    cfg.model.flash_attention = True
    cfg.model.flash_min_tokens = 512
    vit = build_backbone(cfg.model, 10)
    assert vit.use_flash is True
    assert vit.flash_min_tokens == 512


def test_ln_bf16_stays_close_to_f32_recipe():
    """`--ln_bf16` (VERDICT r3 #5 bandwidth experiment) changes only the
    LayerNorm compute dtype; in f32 compute the flag must be a no-op, and
    in bf16 compute its outputs must track the f32-LN recipe to bf16
    resolution — it is a perf lever, not a different model."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, 32, 3)),
                    jnp.float32)

    def logits(ln_bf16, dtype):
        model = build_vit("vit_t16_d4", num_classes=7, dtype=dtype,
                          ln_bf16=ln_bf16)
        v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                       train=False)
        return np.asarray(model.apply(v, x, train=False), np.float32)

    # f32 compute: flag is exactly a no-op (ln dtype == compute dtype)
    np.testing.assert_array_equal(logits(False, jnp.float32),
                                  logits(True, jnp.float32))
    # bf16 compute: bf16 LN tracks the f32-LN recipe to bf16 resolution
    a, b = logits(False, jnp.bfloat16), logits(True, jnp.bfloat16)
    np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
    assert np.std(a) > 1e-3


def test_vit_remat_checkpoint_dots_gradients_match():
    """remat with the checkpoint_dots policy must stay numerically
    transparent (same contract tests/test_remat.py pins for ResNet)."""
    import optax

    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray([1, 3], jnp.int32)

    def grads_for(remat):
        model = build_vit("vit_t16_d4", num_classes=5, dtype=jnp.float32,
                          remat=remat)
        v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                       train=False)

        def loss(params):
            logits = model.apply({"params": params}, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        return jax.jit(jax.grad(loss))(v["params"])  # one program, not an op at a time

    for a, b in zip(jax.tree_util.tree_leaves(grads_for(False)),
                    jax.tree_util.tree_leaves(grads_for(True))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
