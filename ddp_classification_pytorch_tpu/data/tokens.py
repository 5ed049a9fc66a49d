"""`--dataset tokens`: a flat file of int32 token ids, memory-mapped and cut
into rows of T + 1 — row i gives (ids[0:T], ids[1:T+1]): the inputs and, as
the per-position labels, the same ids shifted by one. Rows are packed text:
documents follow one another with no padding, and a row attends across their
boundaries. The loader batches the two halves as it batches images and labels
(`ShardedLoader`: rows of the first item's shape, labels cast to int32), so a
batch is two (B, T) int32 arrays and nothing downstream knows it is not an
image batch.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


class TokenDataset:
    def __init__(self, path: str, seq_len: int):
        if not path:
            raise ValueError("--dataset tokens needs --train_dir <file of int32 ids>")
        self.ids = np.memmap(path, dtype=np.int32, mode="r")
        self.seq_len = int(seq_len)
        self.rows = len(self.ids) // (self.seq_len + 1)
        if self.rows < 1:
            raise ValueError(
                f"{path} holds {len(self.ids)} ids: not one row of "
                f"{self.seq_len} + 1")

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, i: int,
                    rng: Optional[np.random.Generator] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        at = i * (self.seq_len + 1)
        row = np.asarray(self.ids[at:at + self.seq_len + 1])
        return row[:-1], row[1:]
