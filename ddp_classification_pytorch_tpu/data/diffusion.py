"""Block-diffusion noise behind the loader's dataset contract.

`NoisedTokens` wraps a dataset of token rows (data/tokens.py: an item's first
array is the row's first L ids) so that an item is

    (x_0 (L,), [x_t ; j] (2, L))        two int32 arrays

— the loader batches them as it batches images and labels, and a batch
reaches the train step as (B, L) and (B, 2, L) int32. The row is cut into
blocks of `block` tokens; each block draws ONE level j uniform on 1..65,536
(repeated over the block in the second row of the label), t = eps +
(1 − eps) · j / 65,536 ∈ (eps, 1], and each token of the block is replaced by
`mask_id` with probability t, independently: x_t (arXiv:2503.09573 §3, the
linear schedule; one level a block as the two-stream pass needs). The batch
itself carries x_0, x_t and the levels, so whoever holds the two arrays — the
step, an evaluation, the benchmark's reference — reads the same noise.

Draws come from the loader's per-row generator (`rng`: keyed by seed, epoch,
sample and row, so an epoch's noise is its own) or, with none given and
always where `keyed` (the validation set: the same noise every epoch), from a
generator keyed by (`seed`, i). Same seed, same batch.

The noising of a row is one `input.noise` span, inside the loader's
`input.load` (docs/observability.md): a row is thousands of tokens and a
batch a handful of rows.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..obs import spans

LEVELS = 65536      # the grid of noise levels: j / LEVELS, j = 1..LEVELS


def level_of(j, eps: float):
    """t of the integer level j (numpy or jax arrays alike)."""
    return eps + (1.0 - eps) * j / LEVELS


def noise_row(x0: np.ndarray, block: int, mask_id: int, eps: float,
              rng: np.random.Generator) -> np.ndarray:
    """x_0 (L,) → [x_t ; j] (2, L) int32."""
    length = x0.shape[0]
    if length % block:
        raise ValueError(f"a row of {length} tokens is not whole blocks of {block}")
    j = np.repeat(rng.integers(1, LEVELS + 1, size=length // block), block)
    masked = rng.random(length) < level_of(j, eps)
    return np.stack([np.where(masked, mask_id, x0), j]).astype(np.int32)


class NoisedTokens:
    def __init__(self, rows, block: int, mask_id: int, eps: float, seed: int,
                 keyed: bool = False):
        self.rows, self.block, self.mask_id = rows, int(block), int(mask_id)
        self.eps, self.seed, self.keyed = float(eps), int(seed), keyed

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, i: int,
                    rng: Optional[np.random.Generator] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        x0 = np.asarray(self.rows.__getitem__(i, rng)[0])
        with spans.span("input.noise"):
            if rng is None or self.keyed:
                rng = np.random.default_rng((self.seed, 0xD1FF, i))
            return x0, noise_row(x0, self.block, self.mask_id, self.eps, rng)
