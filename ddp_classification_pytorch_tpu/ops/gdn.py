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

What runs where is read from the shapes (`takes_kernel`; no flag):

- the row whole chunks of CHUNK and a head whole sublane tiles, at least
  MIN_WIDTH wide both ways (the published 96 x 192): three Pallas kernels
  under one `jax.custom_vjp`, `ops/kda.py`'s design with the decay a scalar.
  Grid (row, block of HEADS_PER_STEP heads, chunk), the chunk axis last and
  sequential; one grid step builds its chunk's G, the masked factor, A, P and
  the solve and takes the three lines with the state, which lives in a VMEM
  scratch, (d_k, d_v) float32, over the whole row: `gdn_fwd` writes o and no
  state. The backward keeps q, k, v, g and β and nothing else: `gdn_states`
  walks the row again and writes what each chunk STARTS from (S, and
  [W | U'] and (I + A)⁻¹, so that the third kernel does not solve again:
  0.28 MB a head and chunk as tiled, 0.53 GB a layer at 8,192 x 15, live
  inside that layer's backward only), `gdn_bwd` walks the chunks in reverse
  with dS in VMEM and gives dq, dk, dv, dg (through the transpose of the
  cumulative sum) and dβ: the transpose of the forward's arithmetic at its
  operand dtypes. (I + A)⁻¹ is taken WHOLE, by the same block forward substitution
  into the identity, and applied in one float32 product each way: the
  substitution into the 288 columns of [β e^G K | β V], and back through
  them in the backward, cost a third of the kernels' time (builder, PR 50,
  uncommitted: PERF.md §6). The operands are TOKENS-MINOR, (B, H, d, T): on
  the TPU XLA lays every (B, T, 15, d) array of the layer out tokens-minor
  (15 x 96 is no whole tile), so that layout is a bitcast of what the input
  side hands over and of what the output side takes, where heads padded to
  whole tiles in (B, T, H·d') or (B, H, T, d) are a relayout copy of every
  operand each way (7.5 ms a step of `olmoh_tp2_8k`, same source). A head's tile is
  (d, C): no width is padded, the per-token factors scale lanes, and the
  matmuls take their transposed forms. A block of tokens is a lane tile, two
  chunks, which a chunk's grid step and its neighbour's both visit
  (`_half`, `_put_half`); g, β, dg, dβ ride (B, H / heads, heads, T),
  lane-dense. Interpret mode off the TPU, as the flash and KDA kernels.
  `gdn_tokens_minor` is the kernels' entry in their layout; `gdn_chunked`,
  the (B, T, H, d) entry of the model and the tests, transposes at its two
  edges.
- any other shape (narrower heads, a row shorter than a chunk, another chunk
  length): `_chunked` in plain XLA, forward and backward through autodiff
  under `jax.checkpoint` (the five inputs are all it keeps), the heads in
  groups (`kda._grouped`) of the most that divides them within
  `kda.HEAD_GROUP`: 5 of 15, where a greatest common divisor would walk them
  one by one. The kernels' second oracle (the first is the token-by-token
  recurrence).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda
from .kda import (CHUNK, HEAD_GROUP, SUB, _F32, _VMEM_BYTES, _each, _grouped, _inverse,
                  _masks, _mm, _solve, _unit_triangle_inverse, _walk, chunk_of)

HEADS_PER_STEP = 5    # heads a grid step of the kernels takes, or the most under
                      # it that divides them: their chains of small dependent
                      # matmuls fill each other's latencies (PERF.md §6, PR 51:
                      # the op alone forward + backward on the chip 7.1 ms at
                      # 3, 5.9 at 5, 5.5 at 15, which traces and compiles more
                      # than twice as long)
MIN_WIDTH = 64        # of a head, for the kernels: under it the MXU's passes
                      # are mostly empty and plain XLA is no worse
_SUBLANES = 16        # a head's dims are whole sublane tiles, bfloat16's too


def head_group_of(heads: int, most: int = HEAD_GROUP) -> int:
    """The most heads at once, at most `most`, that divide `heads`."""
    return max(d for d in range(1, most + 1) if heads % d == 0)


def takes_kernel(t: int, dk: int, dv: int, chunk: int = CHUNK) -> bool:
    """Rows of `t` tokens and heads (dk, dv) wide go through the kernels:
    where the row is whole chunks of CHUNK and a head is whole sublane tiles
    and at least MIN_WIDTH wide both ways; anything else is `_chunked`'s (and
    `chunk_of` refuses a row it cannot cut before either is asked)."""
    return (chunk_of(t, chunk) == CHUNK and min(dk, dv) >= MIN_WIDTH
            and dk % _SUBLANES == 0 and dv % _SUBLANES == 0)


def gdn_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                beta: jnp.ndarray, *, chunk: int = CHUNK, dtype=jnp.bfloat16,
                head_group: int = HEAD_GROUP):
    """q, k (B, T, H, d_k), v (B, T, H, d_v), g (B, T, H) the log of the
    head's decay (≤ 0), beta (B, T, H) → o (B, T, H, d_v) float32. The row is
    whole chunks or one shorter chunk (`kda.chunk_of` refuses any other). The
    kernels where `takes_kernel` says so (through `gdn_tokens_minor`: a
    transpose each way, which is no copy where the (B, T, H, d) arrays are
    laid out tokens-minor, as XLA lays them out on the TPU), else `_chunked`.
    The kernels round q, k, v to `dtype` at their matmuls' operands, whatever
    dtype they come in, and hand dq, dk, dv back in that dtype: the model
    hands them over in float32 (`DecoderLayer._gdn`), one rounding fewer each
    way than `_chunked` takes (on the cell's hardest known seed the worst
    leaf's distance from the float32 reference read 0.0130 with bf16 ones,
    builder, PR 50, and 0.0080 so: PERF.md §6, PR 51)."""
    if takes_kernel(k.shape[1], k.shape[-1], v.shape[-1], chunk):
        o = gdn_tokens_minor(*(x.transpose(0, 2, 3, 1) for x in (q, k, v)), g, beta,
                             dtype=dtype)
        return o.transpose(0, 3, 1, 2)
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


# ---------------------------------------------------------------------------
# the kernels: a head's (d_k, d_v) state stays in VMEM over the row
# ---------------------------------------------------------------------------

def _col(x, mask):
    """(1, C) → (C, 1): row t the sum of x over the lanes `mask` keeps in it
    (the diagonal: x transposed; the lower triangle: its cumulative sum)."""
    return jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True)


def _row(x, mask):
    """(C, 1) → (1, C): lane s the sum of x over the rows `mask` keeps in it."""
    return jnp.sum(jnp.where(mask, x, 0.0), axis=0, keepdims=True)


def _half(ref, j, half):
    """Rows j (a head, or a slice of them) of a block whose lanes hold two
    chunks of tokens (one, where the row is one chunk): chunk `half`
    (traced), rolled to the front, float32."""
    x = ref[j].astype(_F32)
    if x.shape[-1] == CHUNK:
        return x
    return pltpu.roll(x, half * CHUNK, 1)[:, :CHUNK]


def _put_half(ref, j, half, x):
    """`_half`'s inverse for an output block, which this chunk's grid step
    and its neighbour's both visit: x (d, C) into chunk `half`'s lanes, the
    other chunk's left as they are."""
    x = x.astype(ref.dtype)
    if ref.shape[-1] == CHUNK:
        ref[j] = x
        return
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 2 * CHUNK), 1)
    ref[j] = jnp.where((lane >= CHUNK) == (half == 1),
                       jnp.concatenate([x, x], axis=1), ref[j])


def _heads(refs, half):
    """A grid step's chunk by head: lists of qᵀ, kᵀ (d_k, C), vᵀ (d_v, C)
    float32 and of g, β (1, C), the tokens along the lanes."""
    heads = range(refs[4].shape[0])
    return (tuple([_half(ref, j, half) for j in heads] for ref in refs[:3])
            + tuple([_half(ref, slice(j, j + 1), half) for j in heads]
                    for ref in refs[3:5]))


def _chunk(q, k, g, beta, dtype):
    """What one chunk builds before its state is asked, as `_chunked` builds
    it, a head each (every line a loop over the heads, so that one head's
    matmuls stand beside the next head's and fill each other's latencies): G
    the in-chunk cumulative sum of g as a column and as a row, G_C − G as a
    row, β as a column (β the row is an operand), e^{G_t − G_s} on and under
    the diagonal (masked BEFORE the exponential), qᵀ beside kᵀ, A before β
    (strictly lower) and P (lower), (C, C) each."""
    c = k[0].shape[1]
    row, col, _ = _masks(c)
    g_col = [_col(x, row == col) for x in g]
    big = [_col(x, col <= row) for x in g]
    big_row = [_row(x, row <= col) for x in g_col]
    tail_row = [_row(x, row > col) for x in g_col]
    since = [jnp.exp(jnp.where(col <= row, x - y, -jnp.inf)) for x, y in zip(big, big_row)]
    kq = [jnp.concatenate([x, y], axis=1) for x, y in zip(k, q)]          # (d_k, 2 C)
    scores = [_mm(x, y, "tn", dtype) for x, y in zip(kq, k)]              # (2 C, C)
    a0 = [jnp.where(col < row, x[:c] * e, 0.0) for x, e in zip(scores, since)]
    p = [x[c:] * e for x, e in zip(scores, since)]
    return big, big_row, tail_row, [_col(x, row == col) for x in beta], since, kq, a0, p


def _whole_inverse(a):
    """(I + A)⁻¹ (C, C) of a head's strictly lower A, in float32 throughout
    (β near 2: PERF.md §6, PR 49): every sub-chunk's own unit triangle
    inverted exactly (`kda._inverse`), then block forward substitution into
    the identity (`kda._solve`). Whole, so that the solve is ONE product with
    [β e^G K | β V] and its transpose one with the cotangent, where a
    substitution into 288 columns walks the sub-chunks again both ways."""
    row, col, _ = _masks(a[0].shape[0])
    return _solve(a, _inverse(a), [(row == col).astype(_F32)] * len(a), None)


def _walk_kernel(*refs, dtype, keep):
    """One chunk of `heads` heads, the chunks in the row's order on the last
    (sequential) grid axis. The state S (d_k, d_v) float32 lives in `state`,
    zeroed at the row's first chunk. Operands and o tokens-minor: a head's
    tile is (d, C), and the chunk's matrices stand as in `_chunked`, so
    (I + A)⁻¹ multiplies from the right. `keep` False (`gdn_fwd`): oᵀ. `keep`
    True (`gdn_states`, the backward's first walk): the state the chunk
    STARTS from, the solve's [W | U']ᵀ and (I + A)⁻¹, which the reverse walk
    reads; no o."""
    ins, outs, state = refs[:5], refs[5:-1], refs[-1]
    heads = range(ins[4].shape[0])
    half = pl.program_id(2) % 2

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    q, k, v, g, beta = _heads(ins, half)
    dk, c = k[0].shape
    big, big_row, tail_row, beta_col, _, _, a0, p = _chunk(q, k, g, beta, dtype)
    inv = _whole_inverse(_each(jnp.multiply, a0, beta_col))
    since_start = _each(jnp.exp, big_row)
    wu = [_mm(jnp.concatenate([x * (s * b), y * b], axis=0), z, "nt")     # (d_k + d_v, C)
          for x, y, s, b, z in zip(k, v, since_start, beta, inv)]
    st = [state[j] for j in heads]
    u = [x[dk:] - _mm(y, x[:dk], "tn", dtype) for x, y in zip(wu, st)]   # (d_v, C)
    if keep:
        for ref, xs in zip(outs, (st, wu, inv)):
            for j in heads:
                ref[j] = xs[j]
    else:
        o = [_mm(y, x * s, "tn", dtype) + _mm(w, z, "nt", dtype)
             for x, s, y, z, w in zip(q, since_start, st, p, u)]
        for j in heads:
            _put_half(outs[0], j, half, o[j])
    new = [jnp.exp(b[c - 1:c]) * y + _mm(x * jnp.exp(e), w, "nt", dtype)
           for b, y, x, e, w in zip(big, st, k, tail_row, u)]
    for j in heads:
        state[j] = new[j]


def _reverse_kernel(*refs, dtype):
    """One chunk of `heads` heads, the chunks in REVERSE on the last grid
    axis; `dstate` carries dS (d_k, d_v), the cotangent of the state the chunk
    ENDS in, zero at the row's last chunk. Every gradient is the transpose of
    `_walk_kernel`'s arithmetic with its operand dtypes: the cotangents enter
    the matmuls in `dtype`, the solve's part in float32. An exponent's
    gradient is its operand times the operand's own gradient; G's reaches g
    through the transpose of the cumulative sum."""
    ins = refs[:5]
    do_ref, st_ref, wu_ref, inv_ref = refs[5:9]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate = refs[9:]
    heads = range(ins[4].shape[0])
    half = (pl.num_programs(2) - 1 - pl.program_id(2)) % 2

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, v, g, beta = _heads(ins, half)
    dk, c = k[0].shape
    row, col, _ = _masks(c)
    do = [_half(do_ref, j, half) for j in heads]
    st, wu, inv, dst = ([ref[j] for j in heads]
                        for ref in (st_ref, wu_ref, inv_ref, dstate))
    big, big_row, tail_row, beta_col, since, kq, a0, p = _chunk(q, k, g, beta, dtype)
    since_start, to_end = _each(jnp.exp, big_row), _each(jnp.exp, tail_row)
    decay = [jnp.exp(b[c - 1:c]) for b in big]
    q_start = _each(jnp.multiply, q, since_start)
    k_start = _each(jnp.multiply, k, since_start)
    k_end = _each(jnp.multiply, k, to_end)
    w = [x[:dk] for x in wu]
    u = [x[dk:] - _mm(y, z, "tn", dtype) for x, y, z in zip(wu, st, w)]
    # the three lines with the state, transposed
    d_u = [_mm(x, y, "nn", dtype) + _mm(ds, z, "tn", dtype)
           for x, y, ds, z in zip(do, p, dst, k_end)]
    d_p = [jnp.where(col <= row, _mm(x, y, "tn", dtype), 0.0) for x, y in zip(do, u)]
    dq_start = [_mm(y, x, "nn", dtype) for x, y in zip(do, st)]
    dk_end = [_mm(ds, x, "nn", dtype) for x, ds in zip(u, dst)]
    d_decay = [jnp.sum(jnp.sum(ds * y, axis=1, keepdims=True), axis=0, keepdims=True)
               for ds, y in zip(dst, st)]
    new = [e * ds + _mm(x, y, "nt", dtype) - _mm(z, t, "nt", dtype)
           for e, ds, x, y, z, t in zip(decay, dst, q_start, do, w, d_u)]
    for j in heads:
        dstate[j] = new[j]
    # the solve, transposed: [dW | dU']ᵀ through (I + A)⁻¹, and A's own
    d_rhs = [_mm(jnp.concatenate([-_mm(y, x, "nn", dtype), x], axis=0), z, "nn")
             for x, y, z in zip(d_u, st, inv)]                            # (d_k + d_v, C)
    d_a = [jnp.where(col < row, -_mm(x, y, "tn"), 0.0) for x, y in zip(d_rhs, wu)]
    # A and P, transposed: dk through k·kᵀ's two sides and q·kᵀ's one
    d_since = [x * b * y + z * t for x, b, y, z, t in zip(d_a, beta_col, a0, d_p, p)]
    ds = [jnp.concatenate([x * b * e, y * e], axis=0)                     # (2 C, C)
          for x, b, y, e in zip(d_a, beta_col, d_p, since)]
    d_lhs = [_mm(x, y, "nt", dtype) for x, y in zip(k, ds)]               # (d_k, 2 C)
    d_cols = [_mm(x, y, "nn", dtype) for x, y in zip(kq, ds)]             # (d_k, C)
    for j in heads:
        d_w, d_v = d_rhs[j][:dk], d_rhs[j][dk:]
        dq_j = dq_start[j] * since_start[j] + d_lhs[j][:, c:]
        dk_j = ((beta[j] * since_start[j]) * d_w + dk_end[j] * to_end[j]
                + d_lhs[j][:, :c] + d_cols[j])
        for ref, x in ((dq_ref, dq_j), (dk_ref, dk_j), (dv_ref, beta[j] * d_v)):
            _put_half(ref, j, half, x)
        up = jnp.sum(k_start[j] * d_w, axis=0, keepdims=True)             # (1, C)
        _put_half(dbeta_ref, slice(j, j + 1), half,
                  up + jnp.sum(v[j] * d_v, axis=0, keepdims=True)
                  + _row(jnp.sum(d_a[j] * a0[j], axis=1, keepdims=True), row == col))
        # G_t: e^{G_t} on k's and q's columns, e^{G_t − G_s}'s row and column
        d_big = (jnp.sum(d_since[j], axis=1, keepdims=True)
                 + _col(beta[j] * up
                        + jnp.sum(dq_start[j] * q_start[j], axis=0, keepdims=True)
                        - jnp.sum(d_since[j], axis=0, keepdims=True), row == col))
        # G_C − G_t = the g after t; G_C, the chunk's whole decay, every g
        d_tail = _col(jnp.sum(dk_end[j] * k_end[j], axis=0, keepdims=True), row == col)
        _put_half(dg_ref, slice(j, j + 1), half,
                  _row(d_big, row >= col) + _row(d_tail, row < col)
                  + decay[j] * d_decay[j])


def _call(kernel, name, ins, outs, heads, interpret, reverse=False):
    """One kernel over the grid (row, block of `heads` heads, chunk), the chunk
    axis last and sequential (`reverse`: walked from the row's end), with one
    (d_k, d_v) float32 scratch a head. `ins` (`_operands` first) and `outs`
    are (array or its ShapeDtypeStruct, kind): "minor" (B, H, d, T) and
    "tokens" (B, H / heads, heads, T), the tokens along the lanes in blocks
    of two chunks (a lane tile) which a chunk's grid step and its neighbour's
    both visit; "chunk" (B, H, NT, r, w) one (r, w) tile a head and chunk."""
    b, h, dk, t = ins[1][0].shape
    dv = ins[2][0].shape[2]
    nt, block = t // CHUNK, min(2 * CHUNK, t)

    def tokens(i, j, c):   # the block of two chunks that holds chunk c
        return i, j, 0, (nt - 1 - c if reverse else c) * CHUNK // block

    def spec(x, kind):
        if kind == "minor":
            return pl.BlockSpec((None, heads, x.shape[2], block), tokens,
                                memory_space=pltpu.VMEM)
        if kind == "tokens":
            return pl.BlockSpec((None, None, heads, block), tokens,
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((None, heads, None, *x.shape[-2:]),
                            lambda i, j, c: (i, j, nt - 1 - c if reverse else c, 0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        out_shape=[x for x, _ in outs],
        grid=(b, h // heads, nt),
        in_specs=[spec(x, kind) for x, kind in ins],
        out_specs=[spec(x, kind) for x, kind in outs],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(*(x for x, _ in ins))


def _operands(q, k, v, g, beta, heads):
    """q, k, v (B, H, d, T) as they are; g and β (B, T, H) → (B, H / heads,
    heads, T) float32, a grid step's heads' tokens along the lanes: lane-dense
    ((B, H, T, 1) would pad every number to a row of 128)."""
    def tokens(x):
        b, t, h = x.shape
        return x.astype(_F32).reshape(b, t, h // heads, heads).transpose(0, 2, 3, 1)

    return [(x, "minor") for x in (q, k, v)] + [(tokens(x), "tokens") for x in (g, beta)]


# jitted, so that the layers of a model share one trace and one lowering of
# each kernel (as ops/kda.py's); `interpret` is in the key: the tests steer it
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _forward(q, k, v, g, beta, *, dtype, interpret):
    heads = head_group_of(beta.shape[-1], HEADS_PER_STEP)
    (o,) = _call(functools.partial(_walk_kernel, dtype=dtype, keep=False), "gdn_fwd",
                 _operands(q, k, v, g, beta, heads),
                 [(jax.ShapeDtypeStruct(v.shape, _F32), "minor")], heads, interpret)
    return o


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _backward(q, k, v, g, beta, do, *, dtype, interpret):
    b, t, h = beta.shape
    heads = head_group_of(h, HEADS_PER_STEP)
    dk, dv, nt = k.shape[2], v.shape[2], t // CHUNK
    ins = _operands(q, k, v, g, beta, heads)
    kept = _call(functools.partial(_walk_kernel, dtype=dtype, keep=True), "gdn_states",
                 ins, [(jax.ShapeDtypeStruct((b, h, nt, r, w), _F32), "chunk")
                       for r, w in ((dk, dv), (dk + dv, CHUNK), (CHUNK, CHUNK))],
                 heads, interpret)
    # the five gradients have the five operands' shapes and dtypes
    dq, dk_, dv_, dg, dbeta = _call(
        functools.partial(_reverse_kernel, dtype=dtype), "gdn_bwd",
        ins + [(do.astype(_F32), "minor")] + [(x, "chunk") for x in kept],
        [(jax.ShapeDtypeStruct(x.shape, x.dtype), kind) for x, kind in ins],
        heads, interpret, reverse=True)

    def per_token(x):   # `_operands`' tokens, back: (B, H / heads, heads, T) -> (B, T, H)
        return x.transpose(0, 3, 1, 2).reshape(b, t, h)

    return dq, dk_, dv_, per_token(dg).astype(g.dtype), per_token(dbeta).astype(beta.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernels(q, k, v, g, beta, dtype):
    return _forward(q, k, v, g, beta, dtype=dtype, interpret=kda._interpret())


def _kernels_fwd(q, k, v, g, beta, dtype):
    return _kernels(q, k, v, g, beta, dtype), (q, k, v, g, beta)


def _kernels_bwd(dtype, res, do):
    return _backward(*res, do, dtype=dtype, interpret=kda._interpret())


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gdn_tokens_minor(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     beta: jnp.ndarray, *, dtype=jnp.bfloat16):
    """The kernels' own entry: q, k (B, H, d_k, T), v (B, H, d_v, T), the
    tokens along the lanes, g and beta (B, T, H) → o (B, H, d_v, T) float32;
    the gradients come back in the same layouts. Only where `takes_kernel`
    says so."""
    (_, _, dk, t), dv = k.shape, v.shape[2]
    if not takes_kernel(t, dk, dv):
        raise ValueError(f"rows of {t} tokens, heads {dk} and {dv} wide: not the "
                         "kernels' (takes_kernel)")
    return _kernels(q, k, v, g, beta, jnp.dtype(dtype))
