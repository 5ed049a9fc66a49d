"""CheckpointManager unit tests: async writes, pruning, best policy, resume."""

import numpy as np

import jax.numpy as jnp
import pytest
from tiny import tiny_cfg

from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager
from ddp_classification_pytorch_tpu.train.state import TrainState


def _state(v: float) -> TrainState:
    return TrainState(
        step=jnp.asarray(int(v)),
        params={"w": jnp.full((4,), v)},
        batch_stats={"m": jnp.zeros((2,))},
        opt_state=(),
    )


def test_async_save_and_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    for e in range(3):
        mgr.save(_state(float(e)), e, metric=float(e))
    mgr.wait()
    assert sorted(mgr._epoch_checkpoints()) == [0, 1, 2]

    restored, next_epoch = mgr.restore_latest(_state(-1.0))
    assert next_epoch == 3
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.full((4,), 2.0))
    # best tracks the max metric
    meta = mgr.read_meta()
    assert meta["best_epoch"] == 2 and meta["best_metric"] == 2.0


def test_keep_prunes_old_epochs(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for e in range(5):
        mgr.save(_state(float(e)), e)
    mgr.wait()
    assert sorted(mgr._epoch_checkpoints()) == [3, 4]


def test_keep_prunes_under_async(tmp_path):
    # pruning must run AFTER the in-flight write lands, or retention is keep+1
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for e in range(5):
        mgr.save(_state(float(e)), e)
    mgr.wait()
    assert sorted(mgr._epoch_checkpoints()) == [3, 4]


def test_best_epoch_writes_identical_bytes_once(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=0)
    mgr.save(_state(3.0), 0, metric=1.0)  # epoch file AND best in one save
    mgr.wait()
    a = (tmp_path / "ckpt_e0.msgpack").read_bytes()
    b = (tmp_path / "ckpt_best.msgpack").read_bytes()
    assert a == b and len(a) > 0


def test_async_write_failure_surfaces(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(_state(0.0), 0)
    mgr.wait()
    import os
    import shutil

    shutil.rmtree(tmp_path)  # make the next write fail
    mgr.save(_state(1.0), 1)
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="async checkpoint write failed"):
        mgr.wait()
    os.makedirs(tmp_path, exist_ok=True)


def test_async_failure_surfaces_on_next_save_and_then_clears(tmp_path):
    """wait() re-raises an async write failure exactly once — including the
    implicit wait() at the head of the NEXT save — and a later wait() must
    not re-raise a failure that was already surfaced."""
    import shutil

    import pytest as _pytest

    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(_state(0.0), 0)
    mgr.wait()
    shutil.rmtree(tmp_path)  # make the next write fail
    mgr.save(_state(1.0), 1)
    mgr._pending.join(timeout=60)  # let the failure land without consuming it
    import os

    os.makedirs(tmp_path, exist_ok=True)
    with _pytest.raises(RuntimeError, match="async checkpoint write failed"):
        mgr.save(_state(2.0), 2)  # the one-in-flight wait() surfaces it
    # surfaced once: the slot is clear, the next save/wait succeed
    mgr.save(_state(3.0), 3)
    mgr.wait()
    assert 3 in mgr._epoch_checkpoints()


def test_read_meta_at_tolerates_any_torn_content(tmp_path):
    """read_meta_at must absorb every torn-file shape — truncated JSON,
    binary garbage (UnicodeDecodeError, not JSONDecodeError), and an empty
    file — or a single bad meta.json crashes every restart identically."""
    meta = tmp_path / "meta.json"
    for content in (b'{"last_epoch": 3, "best_', b"\x80\x81\xfe\xff\x00",
                    b""):
        meta.write_bytes(content)
        assert CheckpointManager.read_meta_at(str(meta)) == {}, content
    assert CheckpointManager.read_meta_at(str(tmp_path / "absent.json")) == {}


def test_meta_lands_after_bytes(tmp_path):
    # meta.json must not claim an epoch whose checkpoint has not hit disk;
    # easiest observable: after wait(), both exist and agree
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(_state(0.0), 7, metric=0.5)
    mgr.wait()
    assert (tmp_path / "ckpt_e7.msgpack").exists()
    assert mgr.read_meta()["last_epoch"] == 7
    assert mgr.read_meta()["best_epoch"] == 7


def test_resume_restores_best_tracking(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(0.0), 0, metric=0.8)
    mgr.save(_state(1.0), 1, metric=0.6)
    mgr.wait()

    mgr2 = CheckpointManager(str(tmp_path))
    _, next_epoch = mgr2.restore_latest(_state(-1.0))
    assert next_epoch == 2
    assert mgr2.best_metric == 0.8
    # a worse metric after resume must NOT become the new best
    assert mgr2.save(_state(2.0), 2, metric=0.55) is False


def test_nan_logits_are_not_hits():
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.utils.metrics import topk_hits

    logits = jnp.array([[jnp.nan, jnp.nan, jnp.nan], [3.0, 1.0, 0.0]])
    labels = jnp.array([0, 0])
    hits = topk_hits(logits, labels, 1)
    assert not bool(hits[0])  # diverged row is a miss, not a perfect score
    assert bool(hits[1])


def test_best_only_policy(tmp_path):
    mgr = CheckpointManager(str(tmp_path), save_every_epoch=True, best_only=True)
    assert mgr.save(_state(0.0), 0, metric=0.5) is True
    assert mgr.save(_state(1.0), 1, metric=0.4) is False  # not a new best
    mgr.wait()
    assert mgr._epoch_checkpoints() == []  # best_only: no per-epoch files
    restored, _ = mgr.restore_latest(_state(-1.0))
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.zeros((4,)))


def test_auto_resume_trainer_e2e(tmp_path):
    """Preemption recovery: a second Trainer with auto_resume picks up the
    latest checkpoint in out_dir and continues from the next epoch — the
    restart command is identical to the start command (scripts/supervise.sh)."""
    from ddp_classification_pytorch_tpu.train.loop import Trainer

    cfg = tiny_cfg("baseline", tmp_path, epochs=2)
    cfg.run.auto_resume = True

    tr = Trainer(cfg)
    assert tr.start_epoch == 0  # fresh dir: auto_resume is a no-op
    tr.train_epoch(0)
    tr.ckpt.save(tr.state, 0, metric=0.5)
    tr.ckpt.wait()
    step_before = int(tr.state.step)

    tr2 = Trainer(cfg)  # "restarted" process, same command
    assert tr2.start_epoch == 1
    assert int(tr2.state.step) == step_before
    assert tr2.ckpt.best_metric == 0.5  # best tracking survives restart
    # the restored state must actually TRAIN: catches sharding mismatches
    # between restored leaves and the jitted step (opt-state momentum must
    # carry mesh-wide NamedShardings, not jit(tx.init)'s single-device ones)
    m = tr2.train_epoch(tr2.start_epoch)
    assert np.isfinite(m["loss"])
    assert int(tr2.state.step) > step_before


def test_torn_meta_json_does_not_brick_resume(tmp_path):
    """meta.json writes are atomic (tmp+replace), and the reader tolerates a
    legacy torn file: a preemption landing mid-meta-write must not crash
    every subsequent --auto_resume attempt identically (the recovery chain
    would be bricked with MAX_RESTARTS exhausted)."""
    from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager

    out = tmp_path / "run"
    out.mkdir()
    (out / "meta.json").write_text('{"last_epoch": 3, "best_')  # torn
    assert CheckpointManager.read_meta_at(str(out / "meta.json")) == {}

    mgr = CheckpointManager(str(out), save_every_epoch=False, best_only=False,
                            keep=0, async_save=False)
    mgr._write_meta(last_epoch=7)  # must replace the torn file atomically
    assert CheckpointManager.read_meta_at(str(out / "meta.json")) == {
        "last_epoch": 7}
    assert not (out / "meta.json.tmp").exists()
