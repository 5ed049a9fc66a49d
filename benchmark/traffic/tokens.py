"""`tokens` generator: packed documents of token ids, made from the seed,
written as a flat int32 file under the cache directory and read by the
program's own `--dataset tokens` (its TokenDataset, its ShardedLoader ->
DevicePrefetcher -> H2D).

Documents have log-normal lengths (median `doc_median`, sigma `doc_sigma`,
clipped to `doc_min`..`doc_max`), each ends with id 0, ids are drawn
Zipf(`zipf_s`) over the configuration's vocabulary (rank = id + 1), and the
stream is cut into `pool_rows` rows of T + 1 with no padding: a row attends
across document boundaries. With Zipf ids the first layer's router sees the
same token often, so expert loads are uneven by construction. The epoch
indexes the pool modulo its rows.

Parameters (the mix's .json): pool_rows, epoch_steps, doc_median, doc_sigma,
doc_min, doc_max, zipf_s.
"""

from __future__ import annotations

import os

import numpy as np


class Modulo:
    """`rows` rows over a smaller dataset, indexed modulo its length (the
    loader's dataset contract: `__getitem__(i, rng)`)."""

    def __init__(self, base, rows: int):
        self.base, self.rows = base, rows

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, i: int, rng=None):
        return self.base[i % len(self.base)]


def make_ids(params: dict, seed: int, total: int, vocab: int) -> np.ndarray:
    """`total` int32 ids of packed documents. Same seed, same ids."""
    rng = np.random.default_rng((seed, 0x70C5))
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** params["zipf_s"]
    ids = rng.choice(vocab, size=total, p=p / p.sum()).astype(np.int32)
    ends = []
    at = 0
    while at < total:   # documents, a few thousand at a time
        lengths = np.clip(np.rint(rng.lognormal(
            np.log(params["doc_median"]), params["doc_sigma"], 4096)),
            params["doc_min"], params["doc_max"]).astype(np.int64)
        stops = at + np.cumsum(lengths)
        ends.append(stops)
        at = int(stops[-1])
    ends = np.concatenate(ends) - 1
    ids[ends[ends < total]] = 0    # every document ends with id 0
    return ids


def path(cache_dir: str, rehearse: bool) -> str:
    return os.path.join(cache_dir, "tokens_rehearse" if rehearse else "tokens",
                        "train.bin")


def argv(params: dict, cache_dir: str, rehearse: bool) -> list:
    """`cli.train` flags this mix adds: the program's token dataset over
    the file `datasets` writes for the run's seed."""
    return ["--dataset", "tokens", "--train_dir", path(cache_dir, rehearse)]


def datasets(params: dict, cfg, seed: int, batch: int, rehearse: bool):
    """(train_ds, val_ds) for `Trainer(cfg, train_ds, val_ds)`."""
    from ddp_classification_pytorch_tpu.data.tokens import TokenDataset

    dc = cfg.model.decoder
    pool = 16 if rehearse else params["pool_rows"]
    steps = 64 if rehearse else params["epoch_steps"]
    file = cfg.data.train_dir
    os.makedirs(os.path.dirname(file), exist_ok=True)
    make_ids(params, seed, pool * (dc.seq_len + 1), dc.vocab_size).tofile(file)
    base = TokenDataset(file, dc.seq_len)
    return Modulo(base, batch * steps), Modulo(base, batch)
