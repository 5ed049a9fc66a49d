"""Gated DeltaNet's recurrence (arXiv:2412.06464, as flash-linear-attention's
`GatedDeltaNet` layer writes it): the gated delta rule with ONE decay a head
and token, in chunked form — the scalar-decay sibling of `ops/kda.py`, whose
decay is a number a channel.

Per head, with q, k (d_k), v (d_v), the log decay g_t ≤ 0 (unbounded below)
and β_t ∈ (0, 2) (`linear_allow_neg_eigval`: I − β k kᵀ may have the
eigenvalue −1 < 1 − β), S ∈ R^{d_k × d_v}, S = 0 at the row's start:

    S_t = e^{g_t} S_{t−1} + β_t k_t (v_t − e^{g_t} S_{t−1}ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t

What the layer around it computes (models/decoder_lm.py::DecoderLayer._gdn;
h the block's input, H heads, 4 taps):

    q' = SiLU(taps_q(h W_q))  k' = SiLU(taps_k(h W_k))  v = SiLU(taps_v(h W_v))
    q  = q' / ||q'||₂ · d_k^−½        k = k' / ||k'||₂             per head
    β_t = 2 σ(h_t W_b)                g_t = −exp(A_log) · softplus(h_t W_a + dt_bias)
    y_t = [RMSNorm_{d_v}(o_t; w) ⊙ SiLU(h_t W_g)] W_o

Chunked (chunks of C tokens, G the in-chunk cumulative sum of g, this token's
included, S_0 the state the chunk starts from):

    A_ts = β_t (k_t·k_s) e^{G_t − G_s}      s < t      (C, C)
    P_ts =     (q_t·k_s) e^{G_t − G_s}      s ≤ t
    (I + A) U = β ⊙ V − (β e^{G} ⊙ K) S_0  =:  U' − W S_0 after the solve
    O   = (e^{G} ⊙ Q) S_0 + P U
    S_C = e^{G_C} S_0 + (e^{G_C − G} ⊙ K)ᵀ U

`ops/kda.py`'s five lines with Diag(exp g) a scalar. The factor e^{G_t − G_s}
is a (C, C) matrix applied AFTER the products q·k and k·k, so nothing has to
be split between a matmul's two operands: every exponent taken is ≤ 0 (the
upper triangle's, which would be positive, is masked BEFORE the exponential),
and the decay needs no sub-chunks and no lower bound on g. (I + A) is solved
into [β e^{G} K | β V] by block forward substitution over sub-chunks of
`kda.SUB` tokens, a sub-chunk's own unit triangle inverted exactly
(`kda._unit_triangle_inverse`), all of it in float32 at the highest matmul
precision (the powers of a whole chunk's triangle grow before they vanish);
the other matmuls take operands in `dtype` (bf16 on the TPU) with float32
accumulation; decays and state are float32. A, P and the solve are taken for
all chunks at once, the three lines with S_0 are `kda._walk`'s scan.

Plain XLA, forward and backward through autodiff under `jax.checkpoint` (the
five inputs are all it keeps), the heads in groups (`kda._grouped`) of the
most that divides them within `kda.HEAD_GROUP`: 5 of 15, where a greatest
common divisor would walk them one by one. No kernel: heads of 96 and 192 are
not whole 128-lane tiles (`kda.takes_kernel`); `CORE_PATH` says "xla".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kda import (CHUNK, HEAD_GROUP, SUB, _grouped, _unit_triangle_inverse,
                  _walk, chunk_of)


# what the recurrence runs as, at every shape (the set-up line's `gdn_core=`)
CORE_PATH = "xla"


def head_group_of(heads: int, most: int = HEAD_GROUP) -> int:
    """The most heads at once, at most `most`, that divide `heads`."""
    return max(d for d in range(1, most + 1) if heads % d == 0)


def gdn_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                beta: jnp.ndarray, *, chunk: int = CHUNK, dtype=jnp.bfloat16,
                head_group: int = HEAD_GROUP):
    """q, k (B, T, H, d_k), v (B, T, H, d_v), g (B, T, H) the log of the
    head's decay (≤ 0), beta (B, T, H) → o (B, T, H, d_v) float32. The row is
    whole chunks or one shorter chunk (`kda.chunk_of` refuses any other)."""
    return _grouped(q, k, v, g, beta, chunk=chunk, dtype=dtype, core=_chunked,
                    head_group=head_group_of(k.shape[2], head_group))


def _chunked(q, k, v, g, beta, *, chunk, dtype):
    b, t, h, dk = k.shape
    chunk = chunk_of(t, chunk)
    nt = t // chunk
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    def split(x):   # (B, T, H, d) -> (B, H, NT, C, d)
        return jnp.moveaxis(x.reshape(b, nt, chunk, h, x.shape[-1]), 3, 1)

    q, k, v, g, beta = (split(x.astype(f32))
                        for x in (q, k, v, g[..., None], beta[..., None]))
    big = jnp.cumsum(g, axis=3)                            # G (B, H, NT, C, 1)
    rows, cols = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    # e^{G_t − G_s} on and under the diagonal, 0 above it: masked before the
    # exponential, so that no exponent is positive
    since = jnp.exp(jnp.where(cols <= rows, big - jnp.swapaxes(big, -1, -2), -jnp.inf))
    a = jnp.where(cols < rows, mm("bhntd,bhnsd->bhnts", k, k) * since, 0.0) * beta
    p = mm("bhntd,bhnsd->bhnts", q, k) * since
    since_start = jnp.exp(big)                             # at most 1
    # (I + A)⁻¹ [β e^{G} K | β V] by block forward substitution over
    # sub-chunks of SUB tokens, a sub-chunk's own unit triangle inverted
    # exactly: the whole chunk's triangle through (I + X)(I + X²)… would pass
    # through powers of X that grow before they vanish (β near 2)
    rhs = jnp.concatenate([k * since_start * beta, v * beta], axis=-1)
    sub = min(SUB, chunk)
    starts = range(0, chunk, sub)
    # the sub-chunks' own triangles side by side, inverted in one batch
    inv = _unit_triangle_inverse(
        jnp.stack([a[..., lo:lo + sub, lo:lo + sub] for lo in starts], axis=-3))
    solved = []
    for i, lo in enumerate(starts):
        r = rhs[..., lo:lo + sub, :]
        if lo:
            r = r - jnp.matmul(a[..., lo:lo + sub, :lo], jnp.concatenate(solved, axis=-2),
                               precision=hi)
        solved.append(jnp.matmul(inv[..., i, :, :], r, precision=hi))
    wu = jnp.concatenate(solved, axis=-2)                  # (B, H, NT, C, d_k + d_v)

    def chunks(x):   # (B, H, NT, ...) -> (NT, B, H, ...)
        return jnp.moveaxis(x, 2, 0)

    last = big[..., -1:, :]                                # G at the chunk's end
    xs = (chunks(wu[..., :dk].astype(dtype)), chunks(wu[..., dk:]),
          chunks((q * since_start).astype(dtype)), chunks(p.astype(dtype)),
          chunks((k * jnp.exp(last - big)).astype(dtype)),
          chunks(jnp.exp(last[..., 0, :])))                # (NT, B, H, 1)
    return _walk(xs, dtype)
