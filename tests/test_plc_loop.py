"""PLC correction-loop tests.

The label-correction *algorithms* are unit-tested in test_labelnoise.py; here
we test the LOOP mechanics deterministically — f(x) collection order, label
write-back, δ carry-over — plus an e2e smoke run. (Whether a net repairs
labels on a given task is a research-dynamics property — early-learning vs
memorization — not a framework invariant, so no accuracy-of-repair assertion
on a live net.)"""

import numpy as np
from tiny import tiny_cfg

from ddp_classification_pytorch_tpu.data.synthetic import SyntheticDataset
from ddp_classification_pytorch_tpu.train.plc_loop import PLCTrainer

N = 64  # training images: four steps of 16 an epoch


def _tiny_cfg(tmp_path, epochs=1):
    cfg = tiny_cfg("plc", tmp_path, epochs)
    cfg.optim.lr = 0.01
    cfg.optim.schedule = "constant"
    cfg.plc.warmup_epochs = 0
    cfg.plc.correction = "lrt"
    return cfg


def _datasets(n=N):
    return (SyntheticDataset(n, 32, 4, seed=999),
            SyntheticDataset(32, 32, 4, seed=999, item_offset=n))


def test_correct_labels_flips_by_oracle_predictions(tmp_path, monkeypatch):
    cfg = _tiny_cfg(tmp_path)
    train_ds, val_ds = _datasets()
    tr = PLCTrainer(cfg, train_ds, val_ds)

    clean = train_ds.labels.copy()
    noisy = clean.copy()
    noisy[:16] = (clean[:16] + 1) % 4  # corrupt the first 16
    train_ds.labels = noisy.astype(np.int32)

    # oracle predictions: fully confident in the CLEAN label
    oracle = np.full((N, 4), -10.0, np.float32)
    oracle[np.arange(N), clean] = 10.0
    monkeypatch.setattr(tr, "predict_train_logits", lambda: oracle)

    changed = tr.correct_labels()
    assert changed == 16
    np.testing.assert_array_equal(np.asarray(train_ds.labels), clean)
    # LRT flipped ≥0.1% of labels → δ must NOT grow
    assert tr.delta == cfg.plc.current_delta


def test_delta_grows_when_nothing_corrected(tmp_path, monkeypatch):
    cfg = _tiny_cfg(tmp_path)
    train_ds, val_ds = _datasets()
    tr = PLCTrainer(cfg, train_ds, val_ds)

    labels = np.asarray(train_ds.labels)
    agree = np.full((N, 4), -10.0, np.float32)
    agree[np.arange(N), labels] = 10.0  # predictions agree with labels
    monkeypatch.setattr(tr, "predict_train_logits", lambda: agree)

    assert tr.correct_labels() == 0
    assert tr.delta == cfg.plc.current_delta + cfg.plc.delta_increment


def test_predict_train_logits_order_and_shape(tmp_path):
    cfg = _tiny_cfg(tmp_path)
    # non-multiple of batch size exercises the wrap-padding slice
    train_ds, val_ds = _datasets(36)
    tr = PLCTrainer(cfg, train_ds, val_ds)
    f_x = tr.predict_train_logits()
    assert f_x.shape == (36, 4)
    assert np.isfinite(f_x).all()


def test_plc_e2e_smoke(tmp_path):
    # one epoch: with no warm-up the first epoch already ends in a correction
    tr = PLCTrainer(_tiny_cfg(tmp_path), *_datasets())
    last = tr.run()
    assert np.isfinite(last["loss"])
    assert "corrected" in last and "delta" in last


def test_noise_injection_at_init(tmp_path):
    cfg = _tiny_cfg(tmp_path)
    cfg.plc.noise_type = 1
    train_ds, val_ds = _datasets()
    clean = train_ds.labels.copy()
    rng = np.random.default_rng(5)
    eta = rng.random((N, 4)) * 0.2
    eta[np.arange(N), clean] += 1.0
    eta /= eta.sum(1, keepdims=True)
    tr = PLCTrainer(cfg, train_ds, val_ds, eta=eta)
    assert int((np.asarray(train_ds.labels) != clean).sum()) > 0


def test_plc_auto_resume_restores_labels_and_delta(tmp_path):
    """Preemption recovery for the PLC workload: --auto_resume must carry the
    corrected labels and δ across the restart, not just the model state."""
    cfg = _tiny_cfg(tmp_path)
    cfg.run.save_every_epoch = True
    cfg.run.auto_resume = True

    train_ds, val_ds = _datasets()
    tr = PLCTrainer(cfg, train_ds, val_ds)
    tr.delta = 0.37  # distinguishable carried state
    tr.run()
    labels_after = np.asarray(train_ds.labels).copy()
    delta_after = tr.delta

    tr2 = PLCTrainer(cfg, _datasets()[0], val_ds)
    assert tr2.start_epoch == 1
    assert tr2.delta == delta_after
    np.testing.assert_array_equal(np.asarray(tr2.train_ds.labels), labels_after)


def _write_imagefolder(root, classes=2, per_class=8, size=32):
    from PIL import Image

    rng = np.random.default_rng(0)
    for c in range(classes):
        d = root / f"class{c}"
        d.mkdir(parents=True)
        for i in range(per_class):
            arr = rng.integers(0, 255, (size, size, 3), np.uint8)
            Image.fromarray(arr.astype(np.uint8)).save(d / f"img{i}.png")


def test_predict_pipeline_is_eval_view_with_running_stats(tmp_path):
    """Regression for the round-2 label-collapse bug: f(x) for correction
    must come from the EVAL transform with running BN stats. Measured on a
    97%-val model, the class-sorted scan made batch-stat predictions 63%
    argmax-vs-truth (vs 99% running-stat) and collapsed 19% noise to 74%
    (train/plc_loop.py::_predict_pipeline). Pin the whole contract by
    equivalence: predict_train_logits() must equal a manual eval-mode
    forward over the eval-transformed images in dataset order."""
    _write_imagefolder(tmp_path / "train")
    _write_imagefolder(tmp_path / "val")
    cfg = _tiny_cfg(tmp_path / "out")
    cfg.data.dataset = "imagefolder"
    cfg.data.transform = "cifar"
    cfg.data.train_dir = str(tmp_path / "train")
    cfg.data.val_dir = str(tmp_path / "val")
    cfg.data.num_classes = 2
    cfg.data.batch_size = 8
    tr = PLCTrainer(cfg)

    assert cfg.plc.batch_stat_predictions is False  # running-stat default

    predict_ds, _ = tr._predict_pipeline()
    assert predict_ds is not tr.train_ds  # eval view, not the train dataset
    # the eval view must be deterministic where the train pipeline is not
    img_a = tr.train_ds.__getitem__(0, np.random.default_rng(1))[0]
    img_b = tr.train_ds.__getitem__(0, np.random.default_rng(2))[0]
    assert not np.array_equal(img_a, img_b)  # random crop/flip active
    img_e1 = predict_ds.__getitem__(0, np.random.default_rng(1))[0]
    img_e2 = predict_ds.__getitem__(0, np.random.default_rng(2))[0]
    np.testing.assert_array_equal(img_e1, img_e2)

    f_x = tr.predict_train_logits()
    # manual oracle: eval-transformed images in scan order, eval-mode apply
    # (train=False → running statistics). Any regression to the train
    # transform OR to batch-stat normalization breaks this equivalence.
    # The default uint8 wire defers normalization to the jitted predict
    # step's epilogue, so the oracle applies the same host-side normalize.
    from ddp_classification_pytorch_tpu.data.transforms import normalize

    rng = np.random.default_rng(0)
    imgs = np.stack([predict_ds.__getitem__(i, rng)[0]
                     for i in range(len(predict_ds))])
    if imgs.dtype == np.uint8:
        imgs = np.stack([normalize(x) for x in imgs])
    variables = {"params": tr.state.params, "batch_stats": tr.state.batch_stats}
    manual = tr.model.apply(variables, imgs, train=False)
    np.testing.assert_allclose(f_x, np.asarray(manual), rtol=1e-4, atol=1e-4)


def test_check_bad_images(tmp_path):
    """Corrupt files are reported by relative path; good ones are not
    (reference check_bad_image, PLC/FolderDataset.py:156-184)."""
    import numpy as np
    from PIL import Image

    from ddp_classification_pytorch_tpu.data.plc import check_bad_images

    root = tmp_path / "imgs"
    (root / "cat").mkdir(parents=True)
    Image.fromarray(
        np.zeros((8, 8, 3), np.uint8)).save(root / "cat" / "good.jpg")
    (root / "cat" / "bad.jpg").write_bytes(b"not a jpeg at all")
    bad = check_bad_images(str(root))
    import os
    assert bad == [os.path.join("cat", "bad.jpg")]
