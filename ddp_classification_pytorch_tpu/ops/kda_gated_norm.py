"""Kimi delta attention's output side as one fused op: the per-head RMSNorm
of the recurrence's output, gated by one sigmoid a head, in the layout the
recurrence's kernels write and W_o reads, (B, T, H·d) with a head's 128 dims
on one lane tile. The second half of what ops/kda_prepare.py does for the
input side: with it no (B, T, H, d) array stands anywhere in the layer.

    y = o / sqrt(mean_head(o²) + eps) · scale · gate[h]      → `dtype`

o (B, T, H·d) float32 from `ops/kda.py::kda_flat`, scale (d) one for all heads,
gate (B, T, H) float32 (its sigmoid is 32 lanes wide and stays in XLA): what
`models/decoder_lm.py`'s `RMSNorm(o) * gate[..., None]` does in (B, T, H, d).

Two Pallas kernels under one `jax.custom_vjp`, grid (row, block of ROWS
tokens) with ALL heads a block, so that the gate rides (B, T, H) as it is, a
lane a head (as (B, H, T, 1), the recurrence's layout for β, a block's DMA
moves one useful word in 128 and the op read 3 to 6 times its roofline:
PERF.md §6, PR 46); inside, STEP rows and a head at a time as the input side:
`kda_gated_norm_fwd`, and `kda_gated_norm_bwd`, which keeps the op's inputs
and nothing else and returns do, dgate (a lane reduction a head, stored to
the head's lane) and the sum over B, T and H for the scale (float32, eight
sublanes a block; XLA adds them). `takes_kernel` is the input side's.
Interpret mode off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda
from .kda import _F32, _LANES
from .kda_prepare import ROWS, STEP, _fold, rows_of


def _normed(o_ref, here, lanes, eps):
    o = o_ref[here, lanes]
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    return inv, o * inv


def _forward_kernel(o_ref, gate_ref, scale_ref, y_ref, *, eps):
    def step(i, _):
        here = pl.ds(pl.multiple_of(i * STEP, STEP), STEP)
        for h in range(o_ref.shape[1] // _LANES):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            _, unit = _normed(o_ref, here, lanes, eps)
            y_ref[here, lanes] = (unit * scale_ref[...] * gate_ref[here, h:h + 1]
                                  ).astype(y_ref.dtype)

    jax.lax.fori_loop(0, o_ref.shape[0] // STEP, step, None)


def _backward_kernel(o_ref, gate_ref, scale_ref, dy_ref, do_ref, dgate_ref, sums_ref,
                     *, eps):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def step(i, _):
        here = pl.ds(pl.multiple_of(i * STEP, STEP), STEP)
        for h in range(o_ref.shape[1] // _LANES):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            inv, unit = _normed(o_ref, here, lanes, eps)
            scale = scale_ref[...]
            dy = dy_ref[here, lanes].astype(_F32)
            dgate_ref[here, h:h + 1] = jnp.sum(dy * (unit * scale), axis=-1,
                                               keepdims=True)
            dz = dy * gate_ref[here, h:h + 1]
            sums_ref[:, lanes] += _fold(dz * unit)
            dn = dz * scale
            do_ref[here, lanes] = inv * (
                dn - unit * jnp.mean(dn * unit, axis=-1, keepdims=True))

    jax.lax.fori_loop(0, o_ref.shape[0] // STEP, step, None)


def _call(kernel, name, o, gate, scale, extra, outs, rows, interpret, sums=False):
    """One kernel over the grid (row, block of `rows` tokens), ALL heads a
    block: the gate rides (B, T, H) as it is, a lane a head. Operands: o and
    `extra` by "rows", the gate by "head", the scale whole; `outs` are
    (ShapeDtypeStruct, kind), "sums" an (8, H·d) block a row of the batch that
    stays in VMEM over the row's blocks."""
    b, t, width = o.shape
    vmem = pltpu.VMEM
    spec = {"rows": pl.BlockSpec((None, rows, width), lambda i, c: (i, c, 0),
                                 memory_space=vmem),
            "head": pl.BlockSpec((None, rows, gate.shape[-1]), lambda i, c: (i, c, 0),
                                 memory_space=vmem),
            "sums": pl.BlockSpec((None, 8, width), lambda i, c: (i, 0, 0),
                                 memory_space=vmem)}
    return pl.pallas_call(
        kernel,
        out_shape=[x for x, _ in outs],
        grid=(b, t // rows),
        in_specs=[spec["rows"], spec["head"],
                  pl.BlockSpec((1, _LANES), lambda i, c: (0, 0), memory_space=vmem)]
        + [spec["rows"]] * len(extra),
        out_specs=[spec[kind] for _, kind in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary" if sums else "parallel"),
            vmem_limit_bytes=kda._VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(o, gate.astype(_F32), scale.astype(_F32)[None], *extra)


# jitted, as the recurrence's wrappers: one trace and one lowering a model
@functools.partial(jax.jit, static_argnames=("eps", "dtype", "rows", "interpret"))
def _forward(o, gate, scale, *, eps, dtype, rows, interpret):
    (y,) = _call(functools.partial(_forward_kernel, eps=eps), "kda_gated_norm_fwd",
                 o, gate, scale, (), [(jax.ShapeDtypeStruct(o.shape, dtype), "rows")],
                 rows, interpret)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def _backward(o, gate, scale, dy, *, eps, rows, interpret):
    b, t, width = o.shape
    heads = gate.shape[-1]
    do, dgate, sums = _call(
        functools.partial(_backward_kernel, eps=eps), "kda_gated_norm_bwd",
        o, gate, scale, (dy,),
        [(jax.ShapeDtypeStruct(o.shape, _F32), "rows"),
         (jax.ShapeDtypeStruct(gate.shape, _F32), "head"),
         (jax.ShapeDtypeStruct((b, 8, width), _F32), "sums")], rows, interpret, sums=True)
    return (do.astype(o.dtype), dgate.astype(gate.dtype),
            sums.reshape(-1, heads, width // heads).sum(axis=(0, 1)).astype(scale.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(o, gate, scale, eps, dtype, rows):
    return _forward(o, gate, scale, eps=eps, dtype=dtype, rows=rows,
                    interpret=kda._interpret())


def _fused_fwd(o, gate, scale, eps, dtype, rows):
    return _fused(o, gate, scale, eps, dtype, rows), (o, gate, scale)


def _fused_bwd(eps, dtype, rows, kept, dy):
    return _backward(*kept, dy, eps=eps, rows=rows, interpret=kda._interpret())


_fused.defvjp(_fused_fwd, _fused_bwd)


def kda_gated_norm(o, gate, scale, *, eps: float, dtype=jnp.bfloat16, rows: int = ROWS):
    """o (B, T, H·d) float32, gate (B, T, H) float32, scale (d) → the gated
    per-head RMSNorm (B, T, H·d) in `dtype`. Only where
    `ops/kda_prepare.py::takes_kernel` says so."""
    return _fused(o, gate, scale, float(eps), jnp.dtype(dtype), rows_of(o.shape[1], rows))
