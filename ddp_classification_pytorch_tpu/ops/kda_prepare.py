"""Kimi delta attention's input side as one fused op: everything between the
projections and the recurrence's kernels (ops/kda.py), in the projections' own
layout (B, T, H·d) with a head's 128 dims on one lane tile, so that no
(B, T, H, d) array (another tiling on the TPU) stands between them.

With x_q, x_k, x_v, x_f (B, T, H·d) the four projections' outputs, w_q, w_k,
w_v (L, H·d) the depthwise taps, A_log (H) and dt_bias (H·d):

    c_t   = Σ_j w[j] · x_{t−(L−1)+j}         causal, zeros before the row's
                                             start, a row sees no other row
    y     = SiLU(c)                          float32
    q     = y_q / sqrt(Σ_head y_q² + 1e-6) · d^-0.5      → the inputs' dtype
    k     = y_k / sqrt(Σ_head y_k² + 1e-6)               → the inputs' dtype
    v     = y_v                                          → the inputs' dtype
    g     = LOWER_BOUND · sigmoid(exp(A_log)[h] · (x_f + dt_bias))   float32

which is what `models/decoder_lm.py::kda_prepare_xla` does in plain XLA (the
path every other shape takes, and the tests' reference), at the same
precision: float32 inside, the casts where it makes them, no approximation
of SiLU, sigmoid or rsqrt. β (B, T, H) is 32 lanes wide and stays in XLA.

Two Pallas kernels under one `jax.custom_vjp`, grid (row, block of
HEADS_PER_STEP heads, block of ROWS tokens):

- `kda_prepare_fwd`: a grid step reads its (ROWS, heads · 128) blocks of the
  four projections and, for the taps, the 16 rows before them (a second block
  of the same array; zeros at the row's start), walks the block STEP rows at
  a time and a head at a time (two vregs a value: the chain stays in
  registers), and writes q, k, v, g as the recurrence's kernels read them.
  Every grid step stands alone.
- `kda_prepare_bwd`, by hand: it keeps the op's INPUTS and nothing else,
  reads them again with dq, dk, dv, dg in the kernels' layout, recomputes the
  taps, SiLU and the norms in VMEM, and writes the four projections'
  gradients in (B, T, H·d). A tap's transpose reaches L − 1 rows BACK, so the
  blocks are walked from the row's end and the first rows of the later
  block's d(conv) wait in a VMEM scratch. The sums over B and T (the three
  tap tables, A_log, dt_bias) accumulate in float32 in an output block that
  stays in VMEM over a row's blocks, eight sublanes a sum; XLA adds the
  sublanes and the rows of the batch (1.8 MB).

What runs where is read from the shapes (`takes_kernel`; no flag).
Interpret mode off the TPU, as the recurrence's kernels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda
from .kda import _F32, _LANES, HEADS_PER_STEP, LOWER_BOUND

ROWS = 256      # tokens a grid step; a shorter row is one block
STEP = 16       # rows the kernels' inner loop takes at once: one packed bf16
                # tile, two float32 vregs a head
TAPS = 4        # the published short_conv_kernel_size; another length: XLA
EPS = 1e-6      # in the heads' L2 norms
# the small operands ride one (16, H·d) float32 table, and their gradients'
# sums come back in the same rows: three tap tables, exp(A_log) a lane, dt_bias
_WQ, _WK, _WV, _A, _BIAS, _TABLE = 0, 4, 8, 12, 13, 16
_SUMS = 14


def rows_of(t: int, rows: int = ROWS) -> int:
    return min(rows, t)


def takes_kernel(t: int, d: int, taps: int, rows: int = ROWS) -> bool:
    """Rows of `t` tokens and heads `d` wide go through the fused op: where
    the recurrence takes its kernels (whole chunks), a head is ONE lane tile
    (its norm a reduction inside it), the row is whole blocks of `rows`
    tokens (or one shorter block) cut in whole STEPs, and the taps are the
    published four."""
    block = rows_of(t, rows)
    return (kda.takes_kernel(t, d, d) and d == _LANES and taps == TAPS
            and t % block == 0 and block % STEP == 0)


def _silu_parts(pre):
    sig = jax.nn.sigmoid(pre)
    return sig, pre * sig


def _window(x_ref, halo_ref, first, i, r0, lanes):
    """Rows r0 − 8 .. r0 + STEP of one head's lanes, float32 (8 + STEP, 128):
    the 8 rows before come from the block itself, or at its first STEP from
    the halo (the 16 rows before the block; zeros where the row starts)."""
    before = pl.multiple_of(jnp.maximum(r0 - 16, 0), 16)
    inside = x_ref[pl.ds(before, 16), lanes].astype(_F32)[8:]
    halo = jnp.where(first, 0.0, halo_ref[8:, lanes].astype(_F32))
    return jnp.concatenate(
        [jnp.where(i == 0, halo, inside),
         x_ref[pl.ds(r0, STEP), lanes].astype(_F32)], axis=0)


def _taps(window, table_ref, at, lanes):
    """→ (c, the TAPS shifted views of x it was made of)."""
    views = [window[8 - (TAPS - 1) + j:8 - (TAPS - 1) + j + STEP]
             for j in range(TAPS)]
    pre = 0.0
    for j, x in enumerate(views):   # in `_causal_taps`' order
        pre = pre + table_ref[at + j:at + j + 1, lanes] * x
    return pre, views


def _unit(y):
    inv = jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + EPS)
    return inv, y * inv


def _fold(x):
    """(STEP, 128) → (8, 128): a sum over B and T waits for XLA to add its
    eight sublanes."""
    return sum(x[r:r + 8] for r in range(0, STEP, 8))


def _decay(xf_ref, table_ref, r0, lanes):
    a = table_ref[_A:_A + 1, lanes]
    u = xf_ref[pl.ds(r0, STEP), lanes].astype(_F32) + table_ref[_BIAS:_BIAS + 1, lanes]
    return a, u, jax.nn.sigmoid(a * u)


def _forward_kernel(xq_ref, xk_ref, xv_ref, xf_ref, hq_ref, hk_ref, hv_ref,
                    table_ref, q_ref, k_ref, v_ref, g_ref, *, scale):
    first = pl.program_id(2) == 0
    rows, width = xf_ref.shape

    def step(i, _):
        r0 = pl.multiple_of(i * STEP, STEP)
        here = pl.ds(r0, STEP)
        for h in range(width // _LANES):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            for x_ref, halo_ref, at, out_ref in (
                    (xq_ref, hq_ref, _WQ, q_ref), (xk_ref, hk_ref, _WK, k_ref),
                    (xv_ref, hv_ref, _WV, v_ref)):
                pre, _ = _taps(_window(x_ref, halo_ref, first, i, r0, lanes),
                               table_ref, at, lanes)
                _, y = _silu_parts(pre)
                if at == _WQ:
                    y = _unit(y)[1] * scale
                elif at == _WK:
                    y = _unit(y)[1]
                out_ref[here, lanes] = y.astype(out_ref.dtype)
            g_ref[here, lanes] = LOWER_BOUND * _decay(xf_ref, table_ref, r0, lanes)[2]

    jax.lax.fori_loop(0, rows // STEP, step, None)


def _backward_kernel(xq_ref, xk_ref, xv_ref, xf_ref, hq_ref, hk_ref, hv_ref,
                     table_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                     dxq_ref, dxk_ref, dxv_ref, dxf_ref, sums_ref, later_ref,
                     *, scale):
    """The blocks of a row from its END (grid axis 2 counts from there), the
    STEPs of a block from its end: `later_ref` (3, 8, lanes) holds the first
    8 rows of d(conv) of the STEP after this one, which the taps' transpose
    reads; zeros after the row's end."""
    steps = xf_ref.shape[0] // STEP
    width = xf_ref.shape[1]
    first = pl.program_id(2) == pl.num_programs(2) - 1    # the row's first block

    @pl.when(pl.program_id(2) == 0)
    def _start():
        later_ref[...] = jnp.zeros_like(later_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def step(n, _):
        i = steps - 1 - n
        r0 = pl.multiple_of(i * STEP, STEP)
        here = pl.ds(r0, STEP)
        for h in range(width // _LANES):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            for b, (x_ref, halo_ref, at, d_ref, dx_ref) in enumerate((
                    (xq_ref, hq_ref, _WQ, dq_ref, dxq_ref),
                    (xk_ref, hk_ref, _WK, dk_ref, dxk_ref),
                    (xv_ref, hv_ref, _WV, dv_ref, dxv_ref))):
                pre, views = _taps(_window(x_ref, halo_ref, first, i, r0, lanes),
                                   table_ref, at, lanes)
                sig, y = _silu_parts(pre)
                dy = d_ref[here, lanes].astype(_F32)
                if at != _WV:   # the norm, transposed
                    inv, unit = _unit(y)
                    if at == _WQ:
                        dy = dy * scale
                    dy = inv * (dy - unit * jnp.sum(dy * unit, axis=-1, keepdims=True))
                d_pre = dy * (sig * (1.0 + pre * (1.0 - sig)))
                # tap j read x_{t−(L−1)+j}: x_s takes w[j] · d_pre_{s+(L−1)−j}
                after = jnp.concatenate([d_pre, later_ref[b, :, lanes]], axis=0)
                later_ref[b, :, lanes] = d_pre[:8]
                dx = 0.0
                for j, x in enumerate(views):
                    dx = dx + (table_ref[at + j:at + j + 1, lanes]
                               * after[TAPS - 1 - j:TAPS - 1 - j + STEP])
                    sums_ref[at + j, :, lanes] += _fold(d_pre * x)
                dx_ref[here, lanes] = dx.astype(dx_ref.dtype)
            a, u, sig = _decay(xf_ref, table_ref, r0, lanes)
            dz = dg_ref[here, lanes] * (LOWER_BOUND * sig * (1.0 - sig))
            dxf_ref[here, lanes] = (dz * a).astype(dxf_ref.dtype)
            sums_ref[_A, :, lanes] += _fold(dz * u)
            sums_ref[_BIAS, :, lanes] += _fold(dz * a)

    jax.lax.fori_loop(0, steps, step, None)


def _table(wq, wk, wv, a_log, dt_bias):
    d = dt_bias.shape[0] // a_log.shape[0]
    rows = jnp.concatenate([wq, wk, wv, jnp.repeat(jnp.exp(a_log), d)[None],
                            dt_bias[None]]).astype(_F32)
    return jnp.pad(rows, ((0, _TABLE - rows.shape[0]), (0, 0)))


def _specs(b, t, width, heads, rows, at):
    """The grid and the block specs a kernel's operands take by kind: "rows"
    a (rows, lanes) block of (B, T, H·d), "halo" the 16 rows of the same
    array before it (the row's first block reads its own first 16: the
    kernel puts zeros there), "table" the small operands' lanes. `at` maps
    the grid's last index to the block of the row."""
    lanes = width // heads * math.gcd(HEADS_PER_STEP, heads)
    vmem = pltpu.VMEM
    return (b, width // lanes, t // rows), {
        "rows": pl.BlockSpec((None, rows, lanes),
                             lambda i, j, c: (i, at(c), j), memory_space=vmem),
        "halo": pl.BlockSpec((None, 16, lanes),
                             lambda i, j, c: (i, jnp.maximum(at(c) * (rows // 16) - 1, 0), j),
                             memory_space=vmem),
        "table": pl.BlockSpec((_TABLE, lanes), lambda i, j, c: (0, j),
                              memory_space=vmem),
        "sums": pl.BlockSpec((None, _SUMS, 8, lanes), lambda i, j, c: (i, 0, 0, j),
                             memory_space=vmem),
    }, lanes


# jitted, as the recurrence's wrappers: the layers of a model share one trace
# and one lowering of each kernel; `interpret` is in the key: the tests steer it
@functools.partial(jax.jit, static_argnames=("heads", "rows", "interpret"))
def _forward(xq, xk, xv, xf, wq, wk, wv, a_log, dt_bias, *, heads, rows, interpret):
    b, t, width = xq.shape
    grid, spec, _ = _specs(b, t, width, heads, rows, lambda c: c)
    like = jax.ShapeDtypeStruct(xq.shape, xq.dtype)
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=(width // heads) ** -0.5),
        out_shape=[like, like, like, jax.ShapeDtypeStruct(xq.shape, _F32)],
        grid=grid,
        in_specs=[spec["rows"]] * 4 + [spec["halo"]] * 3 + [spec["table"]],
        out_specs=[spec["rows"]] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=kda._VMEM_BYTES),
        interpret=interpret,
        name="kda_prepare_fwd",
    )(xq, xk, xv, xf, xq, xk, xv, _table(wq, wk, wv, a_log, dt_bias))


@functools.partial(jax.jit, static_argnames=("heads", "rows", "interpret"))
def _backward(xq, xk, xv, xf, wq, wk, wv, a_log, dt_bias, dq, dk, dv, dg, *,
              heads, rows, interpret):
    b, t, width = xq.shape
    last = t // rows - 1
    grid, spec, lanes = _specs(b, t, width, heads, rows, lambda c: last - c)
    like = jax.ShapeDtypeStruct(xq.shape, xq.dtype)
    table = _table(wq, wk, wv, a_log, dt_bias)
    *dx, sums = pl.pallas_call(
        functools.partial(_backward_kernel, scale=(width // heads) ** -0.5),
        out_shape=[like] * 4 + [jax.ShapeDtypeStruct((b, _SUMS, 8, width), _F32)],
        grid=grid,
        in_specs=([spec["rows"]] * 4 + [spec["halo"]] * 3 + [spec["table"]]
                  + [spec["rows"]] * 4),
        out_specs=[spec["rows"]] * 4 + [spec["sums"]],
        scratch_shapes=[pltpu.VMEM((3, 8, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=kda._VMEM_BYTES),
        interpret=interpret,
        name="kda_prepare_bwd",
    )(xq, xk, xv, xf, xq, xk, xv, table, dq, dk, dv, dg.astype(_F32))
    sums = sums.sum(axis=(0, 2))
    d_a_log = (sums[_A] * table[_A]).reshape(heads, -1).sum(axis=-1)
    return (*dx, sums[_WQ:_WK].astype(wq.dtype), sums[_WK:_WV].astype(wk.dtype),
            sums[_WV:_A].astype(wv.dtype), d_a_log.astype(a_log.dtype),
            sums[_BIAS].astype(dt_bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _fused(xq, xk, xv, xf, wq, wk, wv, a_log, dt_bias, heads, rows):
    return tuple(_forward(xq, xk, xv, xf, wq, wk, wv, a_log, dt_bias, heads=heads,
                          rows=rows, interpret=kda._interpret()))


def _fused_fwd(*args):
    return _fused(*args), args[:9]


def _fused_bwd(heads, rows, kept, cotangents):
    return _backward(*kept, *cotangents, heads=heads, rows=rows,
                     interpret=kda._interpret())


_fused.defvjp(_fused_fwd, _fused_bwd)


def kda_prepare(xq, xk, xv, xf, xb, wq, wk, wv, a_log, dt_bias, *, rows: int = ROWS):
    """x_q, x_k, x_v, x_f (B, T, H·d) and x_b (B, T, H) in the compute dtype,
    the taps (4, H·d), A_log (H), dt_bias (H·d) → q, k, v (B, T, H·d) in that
    dtype, g (B, T, H·d) and β (B, T, H) float32, as `ops/kda.py::kda_flat`
    reads them. Only where `takes_kernel` says so."""
    t, heads = xq.shape[1], a_log.shape[0]
    if not takes_kernel(t, xq.shape[2] // heads, wq.shape[0], rows):
        raise ValueError(f"rows of {t} tokens, heads {xq.shape[2] // heads} wide, "
                         f"{wq.shape[0]} taps: not the fused op's (takes_kernel)")
    return (*_fused(xq, xk, xv, xf, wq, wk, wv, a_log, dt_bias, heads, rows_of(t, rows)),
            jax.nn.sigmoid(xb.astype(_F32)))
