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

A, P and the solve of [β K Γ | β V] depend on no state and are taken for all
chunks at once; the three lines with S_0 are a `lax.scan` over the chunks.
exp(G_t − G_s) has to be split between the two operands of a matmul, and one
factor of a split over a whole chunk leaves float32's range (g = −5 over 64
tokens: exp(320)). So the chunk is cut into sub-chunks of SUB = 16 tokens:
the rows of sub-chunk i are taken against R_i, the cumulative decay before
its first token — the row's factor exp(G_t − R_i) is at most 1, an earlier
sub-chunk's column factor exp(R_i − G_s) too, and the own sub-chunk's is at
most exp(16 · 5) = 5.5e34, inside float32 (and bfloat16, which has its
exponent): what the published lower bound of −5 is for. Each sub-chunk's
rows take only the columns up to their own (a loop of C / 16 steps in the
program's text: all pairs through one batched matmul with the later
sub-chunks masked is a third less text and, measured on the chip, 9 % more
step). (I + A) is solved by block forward substitution over the sub-chunks; a
sub-chunk's own 16 x 16 unit triangle is inverted exactly by
(I + X)(I + X²)(I + X⁴)(I + X⁸), X = −A_ii (nilpotent), in float32 at the
highest matmul precision.

Plain XLA, forward and backward through autodiff. Matmul operands in `dtype`
(bf16 on the TPU) with float32 accumulation; the decays, the triangular
solve's inverses and the state in float32. The whole op is a
`jax.checkpoint`: its backward builds the chunks' matrices and walks the
states again from q, k, v, g and β, which are all it keeps (at 8,192 tokens
and 32 heads of 128 what it builds on the way is over a gigabyte, of which
a layer's backward would else hold every piece at once).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SUB = 16              # tokens a sub-chunk
CHUNK = 64            # tokens a chunk; a shorter row is one chunk
HEAD_GROUP = 8        # heads whose chunks and states stand at once: at 8,192
                      # tokens all 32 are 16.6 GB of step by the compiler's
                      # count, and slower on the chip (PERF.md §6, PR 42)
LOWER_BOUND = -5.0    # of g, the published kda_lower_bound: SUB tokens of it are
                      # exp(80), which summed over a head's channels stays
                      # inside float32
# constants until a second published value exists; the tests pass `chunk=` and
# `head_group=`


def chunk_of(t: int, chunk: int = CHUNK) -> int:
    """The chunk a row of `t` tokens is cut into: `chunk`, or the whole of a
    shorter row; it divides the row and is a multiple of SUB or shorter."""
    chunk = min(chunk, t)
    if t % chunk or (chunk % SUB and chunk > SUB):
        raise ValueError(f"rows of {t} tokens in chunks of {chunk}: a chunk "
                         f"divides the row and is a multiple of {SUB} or shorter")
    return chunk


def kda_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                beta: jnp.ndarray, *, chunk: int = CHUNK, dtype=jnp.bfloat16,
                head_group: int = HEAD_GROUP):
    """`_chunked` under `jax.checkpoint` (the module's last paragraph), the
    heads `head_group` at a time (or the most that divides them): one group's
    matrices and states are all that stands, at that many times the steps."""
    h = k.shape[2]
    group = math.gcd(head_group, h)
    one = jax.checkpoint(functools.partial(_chunked, chunk=chunk, dtype=dtype))
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
        x = -a[..., i * sub:]
        inv = jnp.eye(sub, dtype=f32) + x
        for _ in range(max((sub - 1).bit_length() - 1, 0)):  # X², X⁴, …: to X^(sub−1)
            x = jnp.matmul(x, x, precision=hi)
            inv = inv + jnp.matmul(inv, x, precision=hi)
        solved.append(jnp.matmul(inv, r, precision=hi))
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

    def step(state, x):
        w_c, u_c, q_c, p_c, k_c, decay = x
        u = u_c - mm("bhcd,bhde->bhce", w_c, state)
        o = mm("bhcd,bhde->bhce", q_c, state) + mm("bhcs,bhse->bhce", p_c, u)
        state = decay[..., None] * state + mm("bhcd,bhce->bhde", k_c, u)
        return state, o

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, -1).transpose(0, 2, 1, 3)
