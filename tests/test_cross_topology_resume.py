"""Cross-topology resume (VERDICT r3 weak #4 → next #2).

`train/checkpoint.py` promises that resume works on a DIFFERENT mesh
topology as long as shapes match: saves gather every TP-sharded leaf to a
fully-replicated host copy, and restore re-places the numpy leaves onto
whatever shardings the *template* state carries — so a template built on a
new mesh re-shards the restored values for that mesh. Until now that was a
docstring claim; this test makes it a behavioral one, in the fleet shape
it actually happens: a run is preempted, the replacement allocation has a
different device count or a different dp×tp split, and training must
continue as if nothing happened.

Topologies exercised (8-device virtual CPU mesh, conftest):
- save under data=4 × model=2 (TP-sharded ArcFace partial-FC head — the
  interesting case: a leaf that was 2-way sharded must come back 4-way);
- restore under data=2 × model=4 (same device count, different split);
- restore under data=2 × model=2 on FOUR devices (shrunk allocation).

Continuity is asserted against an uninterrupted control: the post-resume
losses replayed on the new topology must match the control's losses for
the same steps (same data, same step-keyed rng) to float32 reduction
tolerance — partitioning changes the reduction ORDER, so equality is
allclose, not bitwise.
"""

import jax
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_train_step

BATCH, CLASSES, SIZE, STEPS, SAVE_AFTER = 16, 64, 16, 4, 2


def _cfg(mp: int):
    cfg = get_preset("arcface")
    cfg.data.image_size = SIZE
    cfg.data.num_classes = CLASSES
    cfg.data.batch_size = BATCH
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.parallel.model_axis = mp
    cfg.parallel.arcface_sharded_ce = mp > 1
    return cfg


def _batches():
    rng = np.random.default_rng(42)
    return [
        (rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32),
         rng.integers(0, CLASSES, BATCH).astype(np.int32))
        for _ in range(STEPS)
    ]


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """Control run on data=4×model=2: save at SAVE_AFTER, keep going."""
    assert len(jax.devices()) >= 8, "conftest must provision 8 CPU devices"
    td = tmp_path_factory.mktemp("xtopo")
    mesh_a = meshlib.make_mesh(meshlib.MeshSpec(4, 2), jax.devices()[:8])
    batches = _batches()
    cfg = _cfg(2)
    with mesh_a:
        model, tx, state = create_train_state(cfg, mesh_a, steps_per_epoch=STEPS)
        step = make_train_step(cfg, model, tx, mesh=mesh_a)
        control_losses = []
        ckpt = CheckpointManager(str(td), async_save=False)
        for i, (images, labels) in enumerate(batches):
            images = jax.device_put(images, meshlib.batch_sharding(mesh_a))
            labels = jax.device_put(labels, meshlib.batch_sharding(mesh_a))
            state, metrics = step(state, images, labels)
            control_losses.append(float(metrics["loss"]))
            if i + 1 == SAVE_AFTER:
                ckpt.save(state, epoch=0, metric=-control_losses[-1])
                ckpt.wait()
    assert all(np.isfinite(control_losses))
    return td, batches, control_losses


def _resume_and_replay(saved, mesh, mp):
    td, batches, control_losses = saved
    cfg = _cfg(mp)
    with mesh:
        model, tx, template = create_train_state(cfg, mesh, steps_per_epoch=STEPS)
        ckpt = CheckpointManager(str(td), async_save=False)
        restored = ckpt.restore(template, ckpt.epoch_path(0))
        assert int(restored.step) == SAVE_AFTER
        # the TP-sharded margin weight must carry the NEW mesh's sharding
        w = restored.params["margin"]["weight"]
        if mp > 1:
            assert w.sharding.spec[0] == meshlib.MODEL_AXIS, w.sharding
            assert w.sharding.mesh.shape[meshlib.MODEL_AXIS] == mp
        step = make_train_step(cfg, model, tx, mesh=mesh)
        losses = []
        state = restored
        for images, labels in batches[SAVE_AFTER:]:
            images = jax.device_put(images, meshlib.batch_sharding(mesh))
            labels = jax.device_put(labels, meshlib.batch_sharding(mesh))
            state, metrics = step(state, images, labels)
            losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(
        losses, control_losses[SAVE_AFTER:], rtol=5e-4, atol=1e-5,
        err_msg=f"post-resume curve diverged on {dict(mesh.shape)}")


def test_resume_same_devices_different_split(saved):
    """data=4×model=2 → data=2×model=4: the head shard width halves."""
    mesh_b = meshlib.make_mesh(meshlib.MeshSpec(2, 4), jax.devices()[:8])
    _resume_and_replay(saved, mesh_b, mp=4)


def test_resume_on_fewer_devices(saved):
    """8 devices → 4 devices (data=2×model=2): the preempt-then-resize
    fleet scenario — the replacement allocation is smaller."""
    mesh_c = meshlib.make_mesh(meshlib.MeshSpec(2, 2), jax.devices()[:4])
    _resume_and_replay(saved, mesh_c, mp=2)


def test_resume_collapses_tp_to_pure_dp(saved):
    """data=4×model=2 → data=8×model=1: the sharded head collapses to the
    dense path (no model axis). Values must still restore; the dense
    ArcFace CE must produce the same losses the partial-FC control did —
    the exactness claim of ops/sharded_head.py applied across a resume."""
    mesh_d = meshlib.make_mesh(meshlib.MeshSpec(8, 1), jax.devices()[:8])
    _resume_and_replay(saved, mesh_d, mp=1)
