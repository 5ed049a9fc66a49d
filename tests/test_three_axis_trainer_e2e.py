"""Trainer-level e2e on the 3-axis (data×model×pipe) mesh.

tests/test_three_axis_pipeline.py pins the train-step math; this locks
the rest of the product surface on the same mesh: the Trainer loop
(config → mesh construction from --pp_stages → epoch → EXACT cross-shard
sharded-CE eval) and preemption recovery — a second Trainer auto-resumes
from the checkpoint, which re-places restored leaves onto 3-axis
shardings (blocks P('pipe'), margin weight P('model')) and must then
actually train.
"""

import jax
import numpy as np
import pytest
from tiny import tiny_cfg

from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.loop import Trainer


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_trainer_runs_and_resumes_on_three_axis_mesh(tmp_path):
    cfg = tiny_cfg("arcface", tmp_path, epochs=2)
    cfg.data.num_classes = 16
    cfg.model.arch = "vit_t16_d4"
    cfg.model.variant = ""
    cfg.model.dropout = 0.0
    cfg.parallel.data_axis = 2
    cfg.parallel.model_axis = 2
    cfg.parallel.pipeline_stages = 2
    cfg.parallel.pipeline_microbatches = 2
    cfg.parallel.arcface_sharded_ce = True
    cfg.run.auto_resume = True

    tr = Trainer(cfg)
    assert dict(tr.mesh.shape) == {"data": 2, "model": 2, "pipe": 2}
    m = tr.train_epoch(0)
    assert np.isfinite(m["loss"])
    ev = tr.evaluate()
    assert np.isfinite(ev["val_loss"])  # sharded-CE eval on the 3-axis mesh
    tr.ckpt.save(tr.state, 0, metric=0.5)
    tr.ckpt.wait()
    step_before = int(tr.state.step)

    tr2 = Trainer(cfg)  # restarted process, same command
    assert tr2.start_epoch == 1
    assert int(tr2.state.step) == step_before
    blocks_leaf = jax.tree_util.tree_leaves(
        tr2.state.params["backbone"]["blocks"])[0]
    assert blocks_leaf.sharding.spec[0] == meshlib.PIPE_AXIS
    w = tr2.state.params["margin"]["weight"]
    assert w.sharding.spec[0] == meshlib.MODEL_AXIS
    m2 = tr2.train_epoch(tr2.start_epoch)  # restored state must TRAIN
    assert np.isfinite(m2["loss"])
    assert int(tr2.state.step) > step_before
