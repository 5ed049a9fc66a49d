"""dp×tp×pp composition: three parallelism axes in ONE train step.

VERDICT r3 #4: every prior multi-axis proof was 2-axis (data × model, one
role per config). This exercises the pentad actually COMPOSING: a ViT
block stack stage-sharded over a dedicated 'pipe' mesh axis
(ops/pipeline.py GPipe ring), an ArcFace margin head class-sharded over
'model' (partial-FC online-softmax CE, ops/sharded_head.py), and the
batch over 'data' — mesh (data=2, model=2, pipe=2) on the 8-device
virtual CPU mesh.

Correctness oracle: the SAME model (same init rng → identical parameter
values) on a 1-axis data=8 mesh, where the pipeline degenerates to a
sequential scan and the dense margin-CE path runs. The 3-axis losses must
match the 1-axis losses step for step — partitioning may only change
float reduction order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_train_step

BATCH, CLASSES, SIZE, STEPS = 16, 64, 32, 3


def _cfg(mp: int, pp: int):
    cfg = get_preset("arcface")
    cfg.data.image_size = SIZE
    cfg.data.num_classes = CLASSES
    cfg.data.batch_size = BATCH
    cfg.model.arch = "vit_t16_d4"
    cfg.model.dtype = "float32"
    cfg.model.dropout = 0.0
    cfg.parallel.model_axis = mp
    cfg.parallel.pipeline_stages = pp
    cfg.parallel.pipeline_microbatches = 2
    cfg.parallel.arcface_sharded_ce = mp > 1
    return cfg


def _losses(mesh, mp, pp):
    cfg = _cfg(mp, pp)
    batches = [
        (np.random.default_rng(10 + i).normal(
            size=(BATCH, SIZE, SIZE, 3)).astype(np.float32),
         np.random.default_rng(20 + i).integers(0, CLASSES, BATCH).astype(np.int32))
        for i in range(STEPS)
    ]
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=STEPS)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        losses = []
        for images, labels in batches:
            images = jax.device_put(images, meshlib.batch_sharding(mesh))
            labels = jax.device_put(labels, meshlib.batch_sharding(mesh))
            state, metrics = step(state, images, labels)
            losses.append(float(metrics["loss"]))
    return losses, state


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_dp_tp_pp_composes_and_matches_single_axis():
    mesh3 = meshlib.make_mesh(meshlib.MeshSpec(2, 2, 2), jax.devices()[:8])
    assert dict(mesh3.shape) == {"data": 2, "model": 2, "pipe": 2}
    losses3, state3 = _losses(mesh3, mp=2, pp=2)
    assert all(np.isfinite(losses3)), losses3

    # the three axes actually hold their assigned roles
    blocks_leaf = jax.tree_util.tree_leaves(
        state3.params["backbone"]["blocks"])[0]
    assert blocks_leaf.sharding.spec[0] == meshlib.PIPE_AXIS, (
        blocks_leaf.sharding)
    w = state3.params["margin"]["weight"]
    assert w.sharding.spec[0] == meshlib.MODEL_AXIS, w.sharding

    # oracle: same params (same seed), 1-axis mesh, dense margin CE,
    # degenerate pipeline (sequential scan)
    mesh1 = meshlib.make_mesh(meshlib.MeshSpec(8, 1, 1), jax.devices()[:8])
    losses1, _ = _losses(mesh1, mp=1, pp=1)
    np.testing.assert_allclose(losses3, losses1, rtol=5e-4, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_gpipe_arcface_inference_scores_match_dense_head():
    """GPipeArcFaceViT's labels=None path must produce exactly the dense
    ArcMarginHead s·cosθ inference scores for the same embeddings — the
    eval contract every arcface workload shares (ARCFACE eval semantics),
    here through the pipelined backbone."""
    from ddp_classification_pytorch_tpu.models.heads import ArcMarginHead
    from ddp_classification_pytorch_tpu.models.pipeline_vit import (
        GPipeArcFaceViT,
    )

    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 1, 2), jax.devices()[:8])
    with mesh:
        model = GPipeArcFaceViT("vit_t16_d4", 11, mesh, microbatches=2,
                                dtype=jnp.float32, axis_name="pipe")
        v = model.init(jax.random.PRNGKey(3), jnp.zeros((1, SIZE, SIZE, 3)))
        x = jnp.asarray(np.random.default_rng(5).normal(
            size=(8, SIZE, SIZE, 3)), jnp.float32)
        scores = np.asarray(model.apply(v, x, None, train=False))
        emb = np.asarray(model.apply(v, x, train=False, method="features"))
    head = ArcMarginHead(num_classes=11, in_features=emb.shape[1])
    ref = head.apply({"params": v["params"]["margin"]}, jnp.asarray(emb), None)
    np.testing.assert_allclose(scores, np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert scores.shape == (8, 11)
