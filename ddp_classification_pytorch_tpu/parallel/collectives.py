"""The explicit-collective model: BatchNorm that names the data axis.

The default train path (`train/steps.py`) lets XLA derive every collective
from shardings. Inside a `shard_map` section nothing is derived: a model
built here carries `axis_name='data'`, so its BatchNorm pmeans the batch
statistics itself (the reference's SyncBatchNorm, SURVEY §2.3). The two
sections of `train/steps.py` (`--grad_reduce_dtype`, `--grad_accum`) build
their model with it; the whole explicit step (per-shard grads, `pmean`,
`psum`'d metrics) that the automatic one is held to is the reference in
`tests/test_collectives.py`.
"""

from __future__ import annotations

from ..config import Config
from ..models.factory import build_model
from .mesh import DATA_AXIS


def build_ddp_model(cfg: Config):
    """Model whose BatchNorm carries the 'data' axis name (explicit SyncBN)."""
    return build_model(cfg.model, cfg.data.num_classes, axis_name=DATA_AXIS)
