"""`pool` generator: a pool of uint8 images made from the seed in memory,
indexed modulo the pool, fed through the program's own ShardedLoader ->
DevicePrefetcher -> H2D. Decode is bypassed; loader and staging are not.

Parameters (the mix's .json): pool_images, epoch_steps.
"""

from __future__ import annotations

import numpy as np


class PoolDataset:
    """`__getitem__(i, rng) -> (HWC uint8 image, label)`, the loader's
    dataset contract. Every seed gets the same number of rows of the same
    size; the seed changes pixels, labels and (through the loader) order."""

    def __init__(self, rows: int, pool: int, image_size: int,
                 num_classes: int, seed: int):
        rng = np.random.default_rng((seed, 0x9001))
        self.images = rng.integers(0, 256, (pool, image_size, image_size, 3),
                                   dtype=np.uint8)
        self.labels = rng.integers(0, num_classes, pool).astype(np.int32)
        self.rows = rows
        self.class_names = [str(i) for i in range(num_classes)]

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, i: int, rng=None):
        j = i % len(self.images)
        return self.images[j], int(self.labels[j])


def argv(params: dict, cache_dir: str, rehearse: bool) -> list:
    """`cli.train` flags this mix adds. `synthetic` = no image transform,
    so the step does not flip."""
    return ["--dataset", "synthetic"]


def datasets(params: dict, cfg, seed: int, batch: int, rehearse: bool):
    """(train_ds, val_ds) for `Trainer(cfg, train_ds, val_ds)`."""
    d = cfg.data
    pool = 64 if rehearse else params["pool_images"]
    steps = 64 if rehearse else params["epoch_steps"]
    train = PoolDataset(batch * steps, pool, d.image_size, d.num_classes, seed)
    val = PoolDataset(batch, min(pool, batch), d.image_size, d.num_classes,
                      seed + 1)
    return train, val
