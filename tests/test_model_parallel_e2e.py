"""ArcFace with a class-sharded head through the full Trainer config path
(cfg.parallel.model_axis=2 on the 8-device mesh → data=4 × model=2)."""

import numpy as np
from tiny import tiny_cfg


from ddp_classification_pytorch_tpu.parallel.mesh import MODEL_AXIS
from ddp_classification_pytorch_tpu.train.loop import Trainer


def test_arcface_model_parallel_trainer(tmp_path):
    cfg = tiny_cfg("arcface", tmp_path)
    cfg.data.image_size = 16
    cfg.data.num_classes = 8  # divisible by model axis
    cfg.parallel.model_axis = 2

    tr = Trainer(cfg)
    assert dict(zip(tr.mesh.axis_names, tr.mesh.devices.shape)) == {
        "data": 4, "model": 2}
    w = tr.state.params["margin"]["weight"]
    assert w.sharding.spec[0] == MODEL_AXIS, w.sharding

    m = tr.train_epoch(0)
    assert np.isfinite(m["loss"])
    val = tr.evaluate()
    assert 0.0 <= val["val_top1"] <= 1.0
    # weight stays sharded after the step (no silent gather)
    w2 = tr.state.params["margin"]["weight"]
    assert w2.sharding.spec[0] == MODEL_AXIS
