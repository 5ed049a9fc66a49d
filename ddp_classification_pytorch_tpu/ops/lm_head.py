"""The vocabulary head of a token decoder and its loss, in row blocks.

Next-token training classifies every position over the vocabulary: at
16,384 positions and 37,984 classes the float32 logits alone are 2.5 GB,
and their cotangent as much again. Here the rows go through the head
`block` at a time inside a `lax.scan` whose body is rematerialized, so one
block's logits (block x V, float32) is all that ever stands — in the forward
pass and, recomputed, in the backward pass. The sums are exact: the loss of
the rows is the sum of the blocks' losses.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def blocked_cross_entropy(h: jnp.ndarray, kernel: jnp.ndarray,
                          targets: jnp.ndarray, block: int,
                          dtype=jnp.bfloat16,
                          weights: Optional[jnp.ndarray] = None):
    """h (N, C) rows, kernel (C, V), targets (N,) → (Σ cross-entropy, count
    of rows whose target is the largest logit, count within the largest 3),
    all f32. `weights` (N,) scales each row's three contributions (0/1 for
    the loader's wrap-padding); `weights` (N, K) gives K sets of sums, each
    of the three (K,), one a column (a looped decoder: the exit distribution
    beside the passes' indicators). The weights may be traced and
    differentiated: their gradient is the row's cross-entropy. The matmul
    runs in `dtype` with f32 accumulation; softmax and the loss are f32."""
    n, c = h.shape
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not divide into blocks of {block}")
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    w = kernel.astype(dtype)

    @jax.checkpoint
    def one(hb, tb, wb):
        logits = jnp.dot(hb.astype(dtype), w, preferred_element_type=jnp.float32)
        at = jnp.take_along_axis(logits, tb[:, None], axis=-1)       # (b, 1)
        ce = jax.nn.logsumexp(logits, axis=-1) - at[:, 0]
        above = jnp.sum(logits > at, axis=-1)    # logits ranked over the target

        def total(x):   # Σ over the block's rows, per column of the weights
            return (jnp.sum(wb * x) if wb.ndim == 1
                    else jnp.sum(wb * x[:, None], axis=0))

        return total(ce), total(above < 1), total(above < 3)

    def body(carry, xs):
        return jax.tree_util.tree_map(jnp.add, carry, one(*xs)), None

    zero = jnp.zeros(weights.shape[1:], jnp.float32)
    sums, _ = jax.lax.scan(
        body, (zero, zero, zero),
        (h.reshape(n // block, block, c), targets.reshape(n // block, block),
         weights.astype(jnp.float32).reshape(n // block, block,
                                             *weights.shape[1:])))
    return sums
