"""End-to-end training tests on the virtual 8-device CPU mesh.

These run the REAL sharded code path — jit over a NamedSharding'd global batch
on 8 devices — which is the test strategy the reference lacks entirely
(SURVEY §4): its DDP scripts cannot even start without CUDA+NCCL.
"""

import numpy as np

import jax

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.train.loop import Trainer


def tiny_cfg(workload: str, epochs: int = 2):
    cfg = get_preset(workload)
    cfg.data.dataset = "synthetic"
    cfg.data.image_size = 32
    cfg.data.num_classes = 4
    cfg.data.synthetic_size = 256
    cfg.data.batch_size = 32
    cfg.data.num_workers = 2
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.run.epochs = epochs
    cfg.run.log_every = 4
    cfg.run.write_records = False
    cfg.run.save_every_epoch = False
    cfg.run.save_best_only = False
    cfg.optim.warmup_iters = 0
    return cfg


def test_baseline_e2e_loss_drops(tmp_path):
    # 6 epochs: the last few train at near-zero loss so the BN running
    # statistics converge to the (now stable) activation distribution —
    # eval mode then matches train mode
    cfg = tiny_cfg("baseline", epochs=6)
    cfg.run.out_dir = str(tmp_path)
    cfg.run.write_records = True
    cfg.optim.lr = 0.05
    tr = Trainer(cfg)
    assert len(jax.devices()) == 8

    first = tr.train_epoch(0)
    for e in range(1, cfg.run.epochs):
        last = tr.train_epoch(e)
    assert last["loss"] < first["loss"], (first, last)

    val = tr.evaluate()
    # 4-class synthetic with strong class means: should be far above chance
    assert val["val_top1"] > 0.5, val
    assert 0.0 <= val["val_top3"] <= 1.0


def test_baseline_records_written(tmp_path):
    cfg = tiny_cfg("baseline", epochs=1)
    cfg.data.synthetic_size = 64
    cfg.run.out_dir = str(tmp_path / "run")
    cfg.run.write_records = True
    tr = Trainer(cfg)
    tr.run()
    assert (tmp_path / "run" / "output.txt").exists()
    assert (tmp_path / "run" / "history.json").exists()
    # the observability scrape file: host 0 rewrites $OUT/metrics.prom
    # atomically at the log cadence and each epoch boundary — a complete
    # Prometheus exposition with the trainer/sentinel instrument families
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    assert "# TYPE train_steps_total counter" in prom
    assert "# TYPE train_epochs_total counter" in prom
    assert "train_epochs_total 1" in prom
    assert "# TYPE sentinel_streak gauge" in prom
    steps_line = [ln for ln in prom.splitlines()
                  if ln.startswith("train_steps_total ")]
    assert steps_line and float(steps_line[0].split()[1]) >= 1


def test_arcface_e2e_smoke(tmp_path):
    cfg = tiny_cfg("arcface", epochs=1)
    cfg.data.synthetic_size = 64
    cfg.run.out_dir = str(tmp_path)
    tr = Trainer(cfg)
    m = tr.train_epoch(0)
    assert np.isfinite(m["loss"])
    val = tr.evaluate()
    assert 0.0 <= val["val_top1"] <= 1.0


def test_nested_e2e_smoke_and_all_k_eval(tmp_path):
    cfg = tiny_cfg("nested", epochs=1)
    cfg.data.synthetic_size = 64
    cfg.optim.warmup_iters = 0
    cfg.run.out_dir = str(tmp_path)
    tr = Trainer(cfg)
    m = tr.train_epoch(0)
    assert np.isfinite(m["loss"])
    val = tr.evaluate()
    assert "best_k" in val and 0 <= val["best_k"] < 512
    assert 0.0 <= val["val_top1"] <= 1.0


def test_cdr_e2e_smoke(tmp_path):
    cfg = tiny_cfg("cdr", epochs=1)
    cfg.data.synthetic_size = 64
    cfg.data.num_classes = 4  # preset sets 100; tiny test overrides
    cfg.data.max_classes = 0
    cfg.run.out_dir = str(tmp_path)
    tr = Trainer(cfg)
    m = tr.train_epoch(0)
    assert np.isfinite(m["loss"])


def test_profiler_window_captures_trace(tmp_path):
    """--profile_steps captures a real jax.profiler trace into
    <out>/profile and deactivates cleanly — the SURVEY §5 tracing
    subsystem (chip_smoke.py takes the same window on the chip)."""
    import os

    cfg = tiny_cfg("baseline", epochs=1)
    cfg.run.out_dir = str(tmp_path)
    cfg.run.profile_steps = 2
    tr = Trainer(cfg)
    tr.run()
    assert tr._prof_active is False
    assert tr._prof_steps == 0  # window closed inside epoch 0
    prof_dir = str(tmp_path / "profile")
    trace_files = [os.path.join(r, f) for r, _, fs in os.walk(prof_dir) for f in fs]
    assert any(f.endswith((".trace.json.gz", ".xplane.pb")) for f in trace_files), (
        f"no trace artifacts under {prof_dir}: {trace_files}")


def test_checkpoint_save_and_resume(tmp_path):
    cfg = tiny_cfg("baseline", epochs=1)
    cfg.data.synthetic_size = 64
    cfg.run.out_dir = str(tmp_path / "ck")
    cfg.run.save_every_epoch = True
    tr = Trainer(cfg)
    tr.run()
    ckpt = tmp_path / "ck" / "ckpt_e0.msgpack"
    assert ckpt.exists()

    # resume into a fresh trainer; params must match bitwise
    cfg2 = tiny_cfg("baseline", epochs=1)
    cfg2.run.out_dir = str(tmp_path / "ck2")
    cfg2.run.resume = str(ckpt)
    tr2 = Trainer(cfg2)
    a = jax.tree_util.tree_leaves(jax.device_get(tr.state.params))
    b = jax.tree_util.tree_leaves(jax.device_get(tr2.state.params))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert tr2.start_epoch == 1
