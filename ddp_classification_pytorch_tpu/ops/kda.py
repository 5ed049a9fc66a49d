"""Kimi delta attention (KDA; Kimi Linear, arXiv:2510.26692 §3): the gated
delta rule with a per-CHANNEL decay, in chunked form — the token mixer that
carries a state along the row where attention keeps every key.

Per head, with q, k (d_k), v (d_v), the log decay g_t ∈ (lower, 0)^{d_k} and
β_t ∈ (0, 1), S ∈ R^{d_k × d_v}, S = 0 at the row's start:

    S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

Chunked (chunks of C tokens; S_0 the state the chunk starts from, G_t the
cumulative log decay inside the chunk, Γ = exp G): with u_t = β_t (v_t −
(Diag(exp g_t) S_{t−1})ᵀ k_t) every S_t = Diag(Γ_t) S_0 + Σ_{s≤t}
Diag(Γ_t / Γ_s) k_s u_sᵀ, and the u of a chunk solve a unit lower-triangular
system:

    A_ts = β_t Σ_c k_tc k_sc exp(G_tc − G_sc)     s < t      (C, C)
    P_ts =     Σ_c q_tc k_sc exp(G_tc − G_sc)     s ≤ t
    (I + A) U = β ⊙ V − (β ⊙ K ⊙ Γ) S_0  =:  U' − W S_0 after the solve
    O   = (Q ⊙ Γ) S_0 + P U
    S_C = Diag(Γ_C) S_0 + (K ⊙ Γ_C / Γ)ᵀ U

A, P and the solve of [β K Γ | β V] depend on no state; the three lines with
S_0 walk the chunks in order. exp(G_t − G_s) has to be split between the two
operands of a matmul, and one factor of a split over a whole chunk leaves
float32's range (g = −5 over 64 tokens: exp(320)). So the chunk is cut into
sub-chunks of SUB = 16 tokens: the rows of sub-chunk i are taken against R_i,
the cumulative decay before its first token — the row's factor exp(G_t − R_i)
is at most 1, an earlier sub-chunk's column factor exp(R_i − G_s) too, and the
own sub-chunk's is at most exp(16 · 5) = 5.5e34, inside float32 (and bfloat16,
which has its exponent): what the published lower bound of −5 is for. Each
sub-chunk's rows take only the columns up to their own. (I + A) is solved by
block forward substitution over the sub-chunks; a sub-chunk's own 16 x 16 unit
triangle is inverted exactly by (I + X)(I + X²)(I + X⁴)(I + X⁸), X = −A_ii
(nilpotent), in float32 at the highest matmul precision. Matmul operands in
`dtype` (bf16 on the TPU) with float32 accumulation; the decays, the
triangles' inverses and the state in float32.

What runs where is read from the shapes (`takes_kernel`; no flag):

- d_k and d_v whole 128-lane tiles and the row whole chunks of CHUNK: three
  Pallas kernels under one `jax.custom_vjp`, grid (row, block of
  HEADS_PER_STEP heads, chunk) with the chunk axis last and sequential. One
  grid step builds its chunk's A, P and solve and takes the three lines with
  the state, which lives in a VMEM scratch (transposed, (d_v, d_k), so that
  the chunk's decay scales its lanes) over the whole row: `kda_fwd` writes o
  and no state. The backward keeps q, k, v, g and β and nothing else: `kda_states`
  walks the row again and writes what each chunk STARTS from (Sᵀ, and [W | U']
  and the triangles' inverses, so that the third kernel does not solve again:
  132 KB a head and chunk, 0.55 GB a layer at 8,192 x 32 x 128, live inside
  that layer's backward only), `kda_bwd` walks the chunks in reverse with dSᵀ
  in VMEM and gives dq, dk, dv, dg (through the transpose of the in-chunk
  cumulative sum) and dβ: the transpose of the forward's arithmetic at its
  operand dtypes. The range argument holds inside the kernels word for word:
  they build the same R_i, the same row and column factors and the same
  per-sub-chunk matmuls (a sub-chunk's columns stop at its own last token:
  the later rows of its column operand are zeros, not factors), so no exp
  there is larger than exp(80); in the backward every exponent's gradient is
  its operand times the operand's own gradient, which multiplies a factor by
  nothing it was not multiplied by forward. The R_i take their gradient like
  any exponent: A and P do not depend on them, but without it what a row's
  and a column's factor round apart would run on to the chunk's first token
  (dg 1.7 % from the recurrence's for 0.9 %). Interpret mode off the TPU, as
  the flash kernels. The kernels read and write (B, T, H · d), a head's dims
  side by side along the lanes: the projections' own layout. `kda_flat` is
  their entry in that layout (the model's: ops/kda_prepare.py hands q, k, v,
  g over in it, ops/kda_gated_norm.py takes o in it, and no (B, T, H, d)
  array, another tiling on the TPU, stands between); `kda_chunked`, the
  (B, T, H, d) entry of the tests and of every shape the input side's fused
  op does not take, reshapes at its two edges.
- any other shape of the per-channel decay (narrower heads, a row shorter
  than a chunk, another chunk length): `_chunked` in plain XLA, forward and
  backward through autodiff,
  under `jax.checkpoint` (its backward builds the chunks' matrices and walks
  the states again from the five inputs, which are all it keeps), HEAD_GROUP
  heads at a time. A, P and the solve are taken for all chunks at once (a
  loop of C / 16 steps in the program's text: all pairs through one batched
  matmul with the later sub-chunks masked is a third less text and, measured
  on the chip, 9 % more step), the three lines with S_0 are a `lax.scan`.
  The kernels' second oracle (the first is the token-by-token recurrence).

A decay that is ONE number a head and token (Gated DeltaNet) is not this
file's: broadcast over the channels it would do d_k exponentials for one and,
unbounded, break the range argument above. It has its own entry and kernels,
`ops/gdn.py` (`gdn_fwd`, `gdn_states`, `gdn_bwd`), which take from here what
does not know the decay's shape: `chunk_of`, `_grouped`, `_walk`, a unit
triangle's exact inverse (`_unit_triangle_inverse`; in the kernels `_inverse`
and `_solve`) and the kernels' small parts (`_mm`, `_masks`, `_each`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16              # tokens a sub-chunk
CHUNK = 64            # tokens a chunk; a shorter row is one chunk
HEAD_GROUP = 8        # heads whose chunks and states stand at once: at 8,192
                      # tokens all 32 are 16.6 GB of step by the compiler's
                      # count, and slower on the chip (PERF.md §6, PR 42)
HEADS_PER_STEP = 8    # heads a grid step of the kernels takes: their chains of
                      # small dependent matmuls fill each other's latencies
                      # (PERF.md §6, PR 43: a layer forward + backward on the
                      # chip 17.9 ms at 2, 11.7 at 4, 10.2 at 8, 9.7 at 16)
LOWER_BOUND = -5.0    # of g, the published kda_lower_bound: SUB tokens of it are
                      # exp(80), which summed over a head's channels stays
                      # inside float32
# constants until a second published value exists; the tests pass `chunk=` and
# `head_group=`
_LANES = 128
_VMEM_BYTES = 64 * 2 ** 20   # of a core's 128 MiB, for a kernel: a grid step's
                             # blocks twice over, and what its schedule spills


def chunk_of(t: int, chunk: int = CHUNK) -> int:
    """The chunk a row of `t` tokens is cut into: `chunk`, or the whole of a
    shorter row; it divides the row and is a multiple of SUB or shorter."""
    chunk = min(chunk, t)
    if t % chunk or (chunk % SUB and chunk > SUB):
        raise ValueError(f"rows of {t} tokens in chunks of {chunk}: a chunk "
                         f"divides the row and is a multiple of {SUB} or shorter")
    return chunk


def takes_kernel(t: int, dk: int, dv: int, chunk: int = CHUNK) -> bool:
    """Rows of `t` tokens and heads (dk, dv) wide go through the kernels:
    where a head's tiles are whole 128-lane tiles and the row is whole chunks
    of CHUNK; anything else is `_chunked`'s (and `chunk_of` refuses a row it
    cannot cut before either is asked)."""
    return (dk % _LANES == 0 and dv % _LANES == 0
            and chunk_of(t, chunk) == CHUNK)


def kda_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                beta: jnp.ndarray, *, chunk: int = CHUNK, dtype=jnp.bfloat16,
                head_group: int = HEAD_GROUP):
    """q, k (B, T, H, d_k), v (B, T, H, d_v), g (B, T, H, d_k) the log of the
    per-channel decay (≤ 0, and ≥ LOWER_BOUND), beta (B, T, H) → o (B, T, H,
    d_v) float32: the kernels where `takes_kernel` says so (through
    `kda_flat`, a reshape each way), else `_grouped`."""
    if takes_kernel(k.shape[1], k.shape[-1], v.shape[-1], chunk):
        b, t = k.shape[:2]
        return kda_flat(*(x.reshape(b, t, -1) for x in (q, k, v, g)), beta,
                        dtype=dtype).reshape(v.shape)
    return _grouped(q, k, v, g, beta, chunk=chunk, dtype=dtype,
                    head_group=head_group)


def _grouped(q, k, v, g, beta, *, chunk=CHUNK, dtype=jnp.bfloat16,
             head_group=HEAD_GROUP, core=None):
    """`core` (`_chunked`; ops/gdn.py hands its own) under `jax.checkpoint`,
    the heads `head_group` at a time (or the most that divides them): one
    group's matrices and states are all that stands, at that many times the
    steps."""
    h = k.shape[2]
    group = math.gcd(head_group, h)
    one = jax.checkpoint(functools.partial(core or _chunked, chunk=chunk, dtype=dtype))
    if group == h:
        return one(q, k, v, g, beta)

    def groups(x):   # (B, T, H, ...) -> (H / group, B, T, group, ...)
        return jnp.moveaxis(
            x.reshape(*x.shape[:2], h // group, group, *x.shape[3:]), 2, 0)

    o = jax.lax.map(lambda xs: one(*xs), tuple(map(groups, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2).reshape(*q.shape[:3], -1)


def _chunked(q, k, v, g, beta, *, chunk, dtype):
    """q, k (B, T, H, d_k), v (B, T, H, d_v), g (B, T, H, d_k) the log of the
    per-channel decay (≤ 0, and ≥ LOWER_BOUND), beta (B, T, H) → o (B, T, H,
    d_v) float32, in chunks of `chunk_of(T, chunk)` tokens."""
    b, t, h, dk = k.shape
    chunk = chunk_of(t, chunk)
    sub = min(SUB, chunk)
    nt, n = t // chunk, chunk // sub
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    def split(x):   # (B, T, H, d) -> (B, H, NT, n, sub, d)
        return jnp.moveaxis(x.reshape(b, nt, n, sub, h, x.shape[-1]), 4, 1)

    q, k, v, g, beta = (split(x.astype(f32)) for x in (q, k, v, g, beta[..., None]))
    # G: cumulative log decay inside the chunk, this token's included;
    # R: the same before the first token of each sub-chunk
    big = jnp.cumsum(g.reshape(b, h, nt, chunk, dk), axis=3).reshape(g.shape)
    ref = big[..., :1, :] - g[..., :1, :]                 # (B, H, NT, n, 1, d_k)
    since_ref, since_start = jnp.exp(big - ref), jnp.exp(big)    # both at most 1
    k_row, q_row = k * since_ref, q * since_ref
    rhs = jnp.concatenate([k * since_start * beta, v * beta], axis=-1)
    rows = jnp.arange(sub)[:, None]
    solved, p_rows = [], []
    for i in range(n):
        # the chunk's tokens up to this sub-chunk's last, against R_i
        k_col = (k[..., :i + 1, :, :] * jnp.exp(ref[..., i:i + 1, :, :]
                                                 - big[..., :i + 1, :, :])
                 ).reshape(b, h, nt, (i + 1) * sub, dk)
        cols = jnp.arange((i + 1) * sub)[None, :]
        a = jnp.where(cols < i * sub + rows,
                      mm("bhntd,bhnsd->bhnts", k_row[..., i, :, :], k_col), 0.0
                      ) * beta[..., i, :, :]
        p = jnp.where(cols <= i * sub + rows,
                      mm("bhntd,bhnsd->bhnts", q_row[..., i, :, :], k_col), 0.0)
        p_rows.append(jnp.pad(p, [(0, 0)] * 4 + [(0, (n - 1 - i) * sub)]))
        r = rhs[..., i, :, :]
        if i:   # block forward substitution
            r = r - mm("bhnts,bhnsd->bhntd", a[..., :i * sub],
                       jnp.concatenate(solved, axis=-2))
        # the sub-chunk's own unit triangle, inverted exactly:
        # (I + X)(I + X²)(I + X⁴)… with X = −A_ii, nilpotent of order sub
        solved.append(jnp.matmul(_unit_triangle_inverse(a[..., i * sub:]), r,
                                 precision=hi))
    wu = jnp.concatenate(solved, axis=-2)                  # (B, H, NT, C, d_k + d_v)
    p = jnp.concatenate(p_rows, axis=-2)                   # (B, H, NT, C, C)

    def whole(x):   # (B, H, NT, n, sub, d) -> (NT, B, H, C, d)
        return jnp.moveaxis(x.reshape(b, h, nt, chunk, x.shape[-1]), 2, 0)

    # what the walk over the chunks reads, in the matmuls' dtype but for U'
    # and the chunk's whole decay: W, U', Q ⊙ Γ, P, K ⊙ Γ_C / Γ, Γ_C
    last = big[..., -1:, -1:, :]                           # G at the chunk's end
    xs = (jnp.moveaxis(wu[..., :dk].astype(dtype), 2, 0),
          jnp.moveaxis(wu[..., dk:], 2, 0),
          whole(q * since_start).astype(dtype), jnp.moveaxis(p.astype(dtype), 2, 0),
          whole(k * jnp.exp(last - big)).astype(dtype),
          jnp.moveaxis(jnp.exp(last[..., 0, 0, :]), 2, 0))

    return _walk(xs, dtype)


def _unit_triangle_inverse(a):
    """(I + A)⁻¹ of strictly lower-triangular A (..., n, n), exactly: with
    X = −A, nilpotent of order n, (I + X)(I + X²)(I + X⁴)… up to X^(n−1), in
    float32 at the highest matmul precision."""
    n, hi = a.shape[-1], jax.lax.Precision.HIGHEST
    x = -a
    inv = jnp.eye(n, dtype=jnp.float32) + x
    for _ in range(max((n - 1).bit_length() - 1, 0)):   # X², X⁴, …: to X^(n−1)
        x = jnp.matmul(x, x, precision=hi)
        inv = inv + jnp.matmul(inv, x, precision=hi)
    return inv


def _walk(xs, dtype):
    """The three lines with S_0, the chunks in order: `xs` = (W, U', Q ⊙ Γ, P,
    K ⊙ Γ_C / Γ, Γ_C), each (NT, B, H, ...) with C rows a chunk but the last,
    the chunk's whole decay (NT, B, H, d_k) → o (B, T, H, d_v) float32. The
    state (B, H, d_k, d_v) float32 starts at zero; matmul operands in `dtype`."""
    f32 = jnp.float32
    nt, b, h, c, dk = xs[0].shape

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    def step(state, x):
        w_c, u_c, q_c, p_c, k_c, decay = x
        u = u_c - mm("bhcd,bhde->bhce", w_c, state)
        o = mm("bhcd,bhde->bhce", q_c, state) + mm("bhcs,bhse->bhce", p_c, u)
        state = decay[..., None] * state + mm("bhcd,bhce->bhde", k_c, u)
        return state, o

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, xs[1].shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, nt * c, -1).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# the kernels: one head's state stays in VMEM over the row
# ---------------------------------------------------------------------------

_F32 = jnp.float32
_FORMS = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mm(a, b, form, dtype=None):
    """a·b ("nn"), a·bᵀ ("nt") or aᵀ·b ("tn"), float32 accumulation: operands
    in `dtype`, or float32 at the highest matmul precision where it is None."""
    dims = (_FORMS[form], ((), ()))
    if dtype is None:
        return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32,
                                   precision=jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=_F32)


def _mm_01(a, b, form):
    """`_mm` of a matrix of zeros and ones `a` with float32 `b`, exactly: b's
    three bf16 parts (they sum to b bit for bit) go through the MXU one pass
    each and no product rounds, where the highest precision would also walk
    the three passes that a's empty lower parts make."""
    parts = []
    for _ in range(3):
        parts.append(b.astype(jnp.bfloat16))
        b = b - parts[-1].astype(_F32)
    return sum(_mm(a, x, form, jnp.bfloat16) for x in parts)


def _padded(x, c):
    """x (r, width) with zero rows after it, to (c, width)."""
    if x.shape[0] == c:
        return x
    return jnp.concatenate([x, jnp.zeros((c - x.shape[0], x.shape[1]), _F32)], axis=0)


def _masks(c):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row, col, (row // SUB) == (col // SUB)


def _each(f, *lists):
    return [f(*xs) for xs in zip(*lists)]


def _scores(q, k, g, dtype):
    """What one chunk builds before its state is asked, exactly as `_chunked`
    builds it, for every head of the grid step (q, k, g: lists of (C, d_k)
    float32, and every line below a loop over the heads, so that one head's
    matmuls stand beside the next head's in the program and fill each
    other's latencies): the cumulative log decay G, the sub-chunks'
    references R_i, the rows against R_i, every sub-chunk's columns (the
    later sub-chunks' rows zero, so no factor is larger than
    exp(SUB · |LOWER_BOUND|)), and from them A before β (strictly lower) and
    P (lower), (C, C) both."""
    c, dk = k[0].shape
    n = c // SUB
    row, col, _ = _masks(c)
    lower = (col <= row).astype(_F32)
    big = [_mm_01(lower, x, "nn") for x in g]          # G: the in-chunk cumsum
    refs = [[b[i * SUB:i * SUB + 1] - x[i * SUB:i * SUB + 1] for i in range(n)]
            for b, x in zip(big, g)]
    since_ref = [jnp.concatenate([jnp.exp(b[i * SUB:(i + 1) * SUB] - r[i])
                                  for i in range(n)], axis=0)
                 for b, r in zip(big, refs)]
    k_row = _each(jnp.multiply, k, since_ref)
    q_row = _each(jnp.multiply, q, since_ref)
    lhs, factors, scores = [], [], []
    for i in range(n):
        lo, hi = i * SUB, (i + 1) * SUB
        factors.append([_padded(jnp.exp(r[i] - b[:hi]), c)
                        for b, r in zip(big, refs)])
        lhs.append([jnp.concatenate([x[lo:hi], y[lo:hi]], axis=0)
                    for x, y in zip(k_row, q_row)])
        scores.append([_mm(x, y * f, "nt", dtype)                  # (2 SUB, C)
                       for x, y, f in zip(lhs[i], k, factors[i])])
    by_head = list(zip(*scores))        # [head][sub-chunk] (2 SUB, C): A's rows, P's
    a0 = [jnp.where(col < row, jnp.concatenate([s[:SUB] for s in sc], axis=0), 0.0)
          for sc in by_head]
    p = [jnp.where(col <= row, jnp.concatenate([s[SUB:] for s in sc], axis=0), 0.0)
         for sc in by_head]
    return big, since_ref, lhs, factors, a0, p


def _diagonal(wide, same):
    """(SUB, C), sub-chunk i's (SUB, SUB) block at lanes [i·SUB, (i+1)·SUB),
    → the (C, C) block-diagonal matrix of those blocks (`same`: `_masks`')."""
    return jnp.where(same, jnp.concatenate([wide] * (wide.shape[1] // SUB), axis=0), 0.0)


def _side_by_side(diagonal):
    """`_diagonal`'s inverse: the row blocks summed (each holds one block)."""
    return sum(diagonal[i:i + SUB] for i in range(0, diagonal.shape[0], SUB))


def _inverse(a):
    """Every sub-chunk's (I + A_ii)⁻¹, exactly, as (I + X)(I + X²)(I + X⁴)…
    with X = −A_ii in float32 at the highest matmul precision, a head each.
    The four triangles ride one matmul: side by side (SUB, C) on the left,
    block-diagonal (C, C) on the right (the zero blocks add exact zeros), so
    SUB rows go through the MXU and not C. → side by side."""
    row, col, same = _masks(a[0].shape[0])
    eye = _side_by_side((row == col).astype(_F32))
    x = [_side_by_side(jnp.where(same, -y, 0.0)) for y in a]
    inv = [eye + y for y in x]
    for _ in range(max((SUB - 1).bit_length() - 1, 0)):
        x = [_mm(y, _diagonal(y, same), "nn") for y in x]
        inv = [y + _mm(y, _diagonal(z, same), "nn") for y, z in zip(inv, x)]
    return inv


def _solve(a, inv, rhs, dtype):
    """(I + A)⁻¹ rhs by block forward substitution over the sub-chunks, a head
    each: earlier sub-chunks' solutions through A's off-diagonal blocks in
    `dtype`, the own triangle's inverse (`inv`: side by side) in float32."""
    c = rhs[0].shape[0]
    _, _, same = _masks(c)
    a_off = [jnp.where(same, 0.0, y) for y in a]
    solved = [[] for _ in a]
    for i in range(c // SUB):
        lo, hi = i * SUB, (i + 1) * SUB
        r = [y[lo:hi] for y in rhs]
        if i:
            r = [y - _mm(z[lo:hi], _padded(jnp.concatenate(done, axis=0), c), "nn", dtype)
                 for y, z, done in zip(r, a_off, solved)]
        for done, y, z in zip(solved, inv, r):
            done.append(_mm(y[:, lo:hi], z, "nn"))
    return [jnp.concatenate(done, axis=0) for done in solved]


def _heads(refs):
    """A grid step's blocks by head, lists of q, k, v, g (C, d) float32 and β
    (C, 1); the heads lie side by side along the lanes."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    heads = beta_ref.shape[0]

    def split(ref):
        d = ref.shape[-1] // heads
        return [ref[:, j * d:(j + 1) * d].astype(_F32) for j in range(heads)]

    return (split(q_ref), split(k_ref), split(v_ref), split(g_ref),
            [beta_ref[j] for j in range(heads)])


def _walk_kernel(*refs, dtype, keep):
    """One chunk of `heads` heads, the chunks in the row's order on the last
    (sequential) grid axis. The state Sᵀ (d_v, d_k) float32 lives in
    `state`, zeroed at the row's first chunk. `keep` False (`kda_fwd`): o.
    `keep` True (`kda_states`, the backward's first walk): the state the chunk
    STARTS from, the solve's [W | U'] and the triangles' inverses, which the
    reverse walk reads; no o."""
    ins, outs, state = refs[:5], refs[5:-1], refs[-1]
    heads = range(ins[4].shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    q, k, v, g, beta = _heads(ins)
    c, dk = k[0].shape
    dv = v[0].shape[-1]
    big, _, _, _, a0, p = _scores(q, k, g, dtype)
    a = _each(jnp.multiply, a0, beta)
    inv = _inverse(a)
    since_start = _each(jnp.exp, big)
    wu = _solve(a, inv, [jnp.concatenate([x * s * b, y * b], axis=1)
                         for x, y, s, b in zip(k, v, since_start, beta)], dtype)
    st = [state[j] for j in heads]
    u = [x[:, dk:] - _mm(x[:, :dk], y, "nt", dtype) for x, y in zip(wu, st)]
    if keep:
        st_ref, wu_ref, inv_ref = outs
        for j in heads:
            st_ref[j] = st[j]
            wu_ref[j] = wu[j]
            inv_ref[j] = inv[j]
    else:
        o = [_mm(x * s, y, "nt", dtype) + _mm(z, w, "nn", dtype)
             for x, s, y, z, w in zip(q, since_start, st, p, u)]
        for j in heads:
            outs[0][:, j * dv:(j + 1) * dv] = o[j]
    last = [b[c - 1:c] for b in big]
    new = [jnp.exp(e) * y + _mm(w, x * jnp.exp(e - b), "tn", dtype)
           for e, y, w, x, b in zip(last, st, u, k, big)]
    for j in heads:
        state[j] = new[j]


def _reverse_kernel(*refs, dtype):
    """One chunk of `heads` heads, the chunks in REVERSE on the last grid
    axis; `dstate` carries dSᵀ (d_v, d_k), the cotangent of the state the
    chunk ENDS in, zero at the row's last chunk. Every gradient is the
    transpose of `_walk_kernel`'s arithmetic with its operand dtypes: the
    cotangents enter the matmuls in `dtype`, the triangles' part in float32.
    Every line a loop over the heads, as in `_scores`."""
    ins = refs[:5]
    do_ref, st_ref, wu_ref, inv_ref = refs[5:9]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate = refs[9:]
    heads = range(ins[4].shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, v, g, beta = _heads(ins)
    c, dk = k[0].shape
    dv = v[0].shape[-1]
    n = c // SUB
    row, col, same = _masks(c)
    do = [do_ref[:, j * dv:(j + 1) * dv] for j in heads]
    st, wu, dst = ([ref[j] for j in heads] for ref in (st_ref, wu_ref, dstate))
    big, since_ref, lhs, factors, a0, p = _scores(q, k, g, dtype)
    a = _each(jnp.multiply, a0, beta)
    since_start = _each(jnp.exp, big)
    last = [b[c - 1:c] for b in big]
    to_end = [jnp.exp(e - b) for e, b in zip(last, big)]
    decay = _each(jnp.exp, last)
    w = [x[:, :dk] for x in wu]
    u = [x[:, dk:] - _mm(y, z, "nt", dtype) for x, y, z in zip(wu, w, st)]
    # the three lines with the state, transposed
    d_u = [_mm(x, y, "tn", dtype) + _mm(z * e, ds, "nt", dtype)
           for x, y, z, e, ds in zip(p, do, k, to_end, dst)]
    d_p = [jnp.where(col <= row, _mm(x, y, "nt", dtype), 0.0) for x, y in zip(do, u)]
    dq = [_mm(x, y, "nn", dtype) * s for x, y, s in zip(do, st, since_start)]
    dk_end = [_mm(x, ds, "nn", dtype) * e for x, ds, e in zip(u, dst, to_end)]
    d_last = [jnp.sum(x * y, axis=0, keepdims=True)
              + e * jnp.sum(ds * z, axis=0, keepdims=True)
              for x, y, e, ds, z in zip(k, dk_end, decay, dst, st)]
    new = [e * ds + _mm(x, y * s, "tn", dtype) - _mm(z, t, "tn", dtype)
           for e, ds, x, y, s, z, t in zip(decay, dst, do, q, since_start, d_u, w)]
    for j in heads:
        dstate[j] = new[j]
    # the solve, transposed: block BACK substitution
    rest = [jnp.concatenate([-_mm(x, y, "nn", dtype), x], axis=1)
            for x, y in zip(d_u, st)]
    inv = [inv_ref[j] for j in heads]
    a_off = [jnp.where(same, 0.0, x) for x in a]
    d_rows = [[None] * n for _ in heads]
    for i in reversed(range(n)):
        lo, hi = i * SUB, (i + 1) * SUB
        for j in heads:
            d_rows[j][i] = _mm(inv[j][:, lo:hi], rest[j][lo:hi], "tn")
        if i:
            rest = [x - _mm(y[lo:hi], z[i], "tn", dtype)
                    for x, y, z in zip(rest, a_off, d_rows)]
    d_rhs = [jnp.concatenate(x, axis=0) for x in d_rows]
    d_a = [jnp.where(col < row, -_mm(x, y, "nt"), 0.0) for x, y in zip(d_rhs, wu)]
    for j in heads:
        dv_ref[:, j * dv:(j + 1) * dv] = (beta[j] * d_rhs[j][:, dk:]).astype(dv_ref.dtype)
        dbeta_ref[j] = (
            jnp.sum(k[j] * since_start[j] * d_rhs[j][:, :dk], axis=1, keepdims=True)
            + jnp.sum(v[j] * d_rhs[j][:, dk:], axis=1, keepdims=True)
            + jnp.sum(d_a[j] * a0[j], axis=1, keepdims=True))
    # A and P, transposed. dk in two parts: through a factor exp(+G) and
    # through a factor exp(−G); an exponent's gradient is its operand times
    # the operand's own gradient, so dG = q ⊙ dq + k ⊙ (dk⁺ − dk⁻). The
    # references take theirs too (`d_refs`): A and P do not depend on R_i, but
    # the rows' and the columns' factors round apart, and what R_i takes
    # stops that difference at the sub-chunk instead of the chunk's start.
    d_a0 = _each(jnp.multiply, d_a, beta)
    dk_up = [b * s * x[:, :dk] for b, s, x in zip(beta, since_start, d_rhs)]
    dk_down = dk_end
    d_lhs, d_refs = [], []
    for i in range(n):
        lo, hi = i * SUB, (i + 1) * SUB
        ds = [jnp.concatenate([x[lo:hi], y[lo:hi]], axis=0)              # (2 SUB, C)
              for x, y in zip(d_a0, d_p)]
        d_lhs.append([_mm(x, y * f, "nn", dtype) for x, y, f in zip(ds, k, factors[i])])
        cols = [_mm(y, z, "tn", dtype) * f for y, z, f in zip(ds, lhs[i], factors[i])]
        dk_down = _each(jnp.add, dk_down, cols)
        # R_i's: the columns' exponents hold +R_i, the rows' −R_i
        d_refs.append([jnp.sum(x * y, axis=0, keepdims=True)
                       - jnp.sum(z * w, axis=0, keepdims=True)
                       for x, y, z, w in zip(k, cols, lhs[i], d_lhs[i])])
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    for j in heads:
        up = dk_up[j] + since_ref[j] * jnp.concatenate([d[j][:SUB] for d in d_lhs], axis=0)
        dq_j = dq[j] + since_ref[j] * jnp.concatenate([d[j][SUB:] for d in d_lhs], axis=0)
        dq_ref[:, j * dk:(j + 1) * dk] = dq_j.astype(dq_ref.dtype)
        dk_ref[:, j * dk:(j + 1) * dk] = (up + dk_down[j]).astype(dk_ref.dtype)
        d_big = q[j] * dq_j + k[j] * (up - dk_down[j])
        # the cumulative sum, transposed; what G's last row takes reaches every
        # g, what R_i takes (G before sub-chunk i) every g before sub-chunk i
        dg_ref[:, j * dk:(j + 1) * dk] = (
            _mm_01((col >= row).astype(_F32), d_big, "nn") + d_last[j]
            + sum(jnp.where(rows < i * SUB, d_refs[i][j], 0.0) for i in range(1, n)))


def _call(kernel, name, ins, outs, interpret, reverse=False):
    """One kernel over the grid (row, head block, chunk), the chunk axis last
    and sequential (`reverse`: walked from the row's end), with one (d_v, d_k)
    float32 scratch a head. `ins` (`_operands` first) and `outs` are (array or
    its ShapeDtypeStruct, kind): "lanes" (B, T, H · d) with a grid step's
    heads side by side, "beta" (B, H, T, 1), "chunk" (B, H, NT, r, w) one
    (r, w) tile a head and chunk."""
    b, t = ins[0][0].shape[:2]
    h = ins[4][0].shape[1]
    dk, dv = ins[1][0].shape[-1] // h, ins[2][0].shape[-1] // h
    heads = math.gcd(HEADS_PER_STEP, h)
    nt = t // CHUNK
    at = (lambda c: nt - 1 - c) if reverse else (lambda c: c)

    def spec(x, kind):
        if kind == "lanes":
            return pl.BlockSpec((None, CHUNK, x.shape[-1] // h * heads),
                                lambda i, j, c: (i, at(c), j), memory_space=pltpu.VMEM)
        if kind == "beta":
            return pl.BlockSpec((None, heads, CHUNK, 1),
                                lambda i, j, c: (i, j, at(c), 0), memory_space=pltpu.VMEM)
        return pl.BlockSpec((None, heads, None, *x.shape[-2:]),
                            lambda i, j, c: (i, j, at(c), 0, 0), memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        out_shape=[x for x, _ in outs],
        grid=(b, h // heads, nt),
        in_specs=[spec(x, kind) for x, kind in ins],
        out_specs=[spec(x, kind) for x, kind in outs],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(*(x for x, _ in ins))


def _operands(q, k, v, g, beta):
    """q, k, v, g (B, T, H · d) as the projections and `ops/kda_prepare.py`
    make them (a head's dims side by side along the lanes: no other layout
    stands on the kernel path), beta (B, T, H)."""
    return [(x, "lanes") for x in (q, k, v, g.astype(_F32))] + [
        (jnp.moveaxis(beta.astype(_F32), 2, 1)[..., None], "beta")]


# jitted, so that the layers of a model share one trace and one lowering of
# each kernel (8 heads unrolled: 1.4 s to trace and 1.5 to lower, every start);
# `interpret` is in the key: the tests steer it
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _forward(q, k, v, g, beta, *, dtype, interpret):
    (o,) = _call(functools.partial(_walk_kernel, dtype=dtype, keep=False), "kda_fwd",
                 _operands(q, k, v, g, beta),
                 [(jax.ShapeDtypeStruct(v.shape, _F32), "lanes")], interpret)
    return o


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _backward(q, k, v, g, beta, do, *, dtype, interpret):
    b, t, h = beta.shape
    dk, dv, nt = k.shape[-1] // h, v.shape[-1] // h, t // CHUNK
    ins = _operands(q, k, v, g, beta)
    kept = _call(functools.partial(_walk_kernel, dtype=dtype, keep=True), "kda_states",
                 ins, [(jax.ShapeDtypeStruct((b, h, nt, r, w), _F32), "chunk")
                       for r, w in ((dv, dk), (CHUNK, dk + dv), (SUB, CHUNK))],
                 interpret)
    # the five gradients have the five operands' shapes and dtypes
    dq, dk_, dv_, dg, dbeta = _call(
        functools.partial(_reverse_kernel, dtype=dtype), "kda_bwd",
        ins + [(do.astype(_F32), "lanes")] + [(x, "chunk") for x in kept],
        [(jax.ShapeDtypeStruct(x.shape, x.dtype), kind) for x, kind in ins],
        interpret, reverse=True)
    return (dq, dk_, dv_, dg.astype(g.dtype),
            jnp.moveaxis(dbeta[..., 0], 1, 2).astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernels(q, k, v, g, beta, dtype):
    return _forward(q, k, v, g, beta, dtype=dtype, interpret=_interpret())


def _kernels_fwd(q, k, v, g, beta, dtype):
    return _kernels(q, k, v, g, beta, dtype), (q, k, v, g, beta)


def _kernels_bwd(dtype, res, do):
    return _backward(*res, do, dtype=dtype, interpret=_interpret())


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kda_flat(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray, *, dtype=jnp.bfloat16):
    """The kernels' own entry: q, k, g (B, T, H · d_k), v (B, T, H · d_v), beta
    (B, T, H) → o (B, T, H · d_v) float32; the gradients come back in the
    same layout. Only where `takes_kernel` says so."""
    h = beta.shape[-1]
    if not takes_kernel(k.shape[1], k.shape[-1] // h, v.shape[-1] // h):
        raise ValueError(f"rows of {k.shape[1]} tokens, heads {k.shape[-1] // h} and "
                         f"{v.shape[-1] // h} wide: not the kernels' (takes_kernel)")
    return _kernels(q, k, v, g, beta, jnp.dtype(dtype))
