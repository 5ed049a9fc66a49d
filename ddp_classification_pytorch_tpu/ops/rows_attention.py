"""Whole-row attention on the qkv projection's own layout — a Pallas kernel pair.

The ViT's rows are short (196 tokens at 224 px): the scores of one head,
(T, T) float32, fit VMEM many times over, so nothing has to stream and
nothing has to be rescaled. What the dense op (ops/attention.py::attention)
pays at such a row is HBM: it writes the (B, H, T, T) scores, walks them for
the softmax, writes the probabilities again in the compute dtype, keeps them
for the backward and walks all of it once more there, and it re-lays q, k and
v head-major first. These two kernels never write a (T, T) array to HBM and
move no operand:

- **Operands in place.** The forward reads the projection's output
  (B, T, 3·H·D) as it stands — q, k and v are column ranges of it — and writes
  o as (B, T, H·D), what the output projection reads. The backward reads the
  same array, dO in o's layout and the saved statistic, and writes d(qkv) as
  ONE (B, T, 3·H·D) array, what the projection's backward reads. One image a
  grid step, every head of it: the blocks are whole rows of the arrays, and
  every slice the kernel takes is a static whole 128-lane tile (two 64-wide
  heads side by side).
- **Keys down, queries across.** A head's scores are built transposed,
  Sᵀ = k·qᵀ·scale (keys on sublanes, queries on lanes), float32. The softmax's
  max and sum over the keys are then sums ACROSS vregs (plain VPU work, no
  lane reduction), its statistic log-sum-exp is a (1, T) lane-dense row that
  goes out as (B, H, T) and comes back broadcast along sublanes, and of the
  five matmuls of the backward only dq needs a transposed operand. The
  probabilities are normalised in float32 and rounded to the compute dtype
  before P·v, exactly as the dense op does (`p.astype(v.dtype)`); every
  matmul accumulates in float32.
- **Heads that share a lane tile** (D < 128) are kept apart by zeroing the
  other heads' lanes of ONE operand of each matmul that contracts over D (k
  for the scores, v for dP); the matmuls whose OUTPUT is D wide run at the
  tile's full 128 lanes, which costs the MXU the same as 64, and the heads'
  results are merged by a lane select. No lane of any operand is shifted.
- **One backward kernel**, in `flash_dkvq`'s shape and with its operand
  dtypes: Sᵀ and Pᵀ = exp(Sᵀ − lse) rebuilt once, dPᵀ = v·dOᵀ,
  Δ = Σ_keys P ⊙ dP (the softmax's own row term, = rowsum(dO ⊙ o), taken where
  the whole row is resident), dSᵀ = Pᵀ ⊙ (dPᵀ − Δ), dv = Pᵀ·dO,
  dk = dSᵀ·q·scale, dq = dS·k·scale.

`rows_supported` is the shape rule: the heads tile the lanes and one image's
blocks and score tiles fit `_VMEM_BUDGET`. Callers ask it; nothing is a flag.
Under a mesh of more than one device the call is wrapped in a `shard_map`
over the batch (the TPU compiler does not partition a Mosaic call; the work
is per image and head). CPU/tests run the same kernels in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..utils.compat import shard_map_unchecked
from .flash_attention import _VMEM_BUDGET, _dot

_LANES = 128
# the compiler's default scoped VMEM: what a call asks for at least
_VMEM_FLOOR = 16 * 2 ** 20
# (T, T) float32 tiles the backward is given room for beside its blocks (S,
# P, dP, dS, their rounded copies and the products, of two heads in flight):
# Mosaic compiled the pair inside the limit this gives at every T tried for a
# described v5e, 4 to 1,024 tokens at ViT-B/16's widths
_SCORE_TILES = 12


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _vmem_bytes(t: int, hd: int, itemsize: int) -> int:
    """VMEM the backward (the larger of the two) needs for one image of `t`
    tokens and heads `hd` wide in all: qkv in, d(qkv) out and dO, each
    double-buffered by the pipeline, beside the score tiles."""
    blocks = 2 * (3 + 3 + 1) * hd * _pad(t, 16) * itemsize
    return blocks + _SCORE_TILES * _pad(t, 8) * _pad(t, _LANES) * 4


def rows_supported(t: int, heads: int, d: int, itemsize: int) -> bool:
    """Whether a row of `t` tokens and `heads` heads of `d` takes the kernel
    pair: the heads fill whole 128-lane tiles (D divides 128 and H·D is a
    multiple of it, or D is a multiple itself) and one image fits
    `_VMEM_BUDGET` (about 1,100 tokens at ViT-B/16's 768 in bf16)."""
    tiles = (d % _LANES == 0) or (_LANES % d == 0 and (heads * d) % _LANES == 0)
    return tiles and _vmem_bytes(t, heads * d, itemsize) <= _VMEM_BUDGET


def _tile(d: int):
    """(lanes a slice of q, k or v spans, heads it holds): a whole 128-lane
    tile of `128 // d` heads, or one head of `d` lanes."""
    width = max(d, _LANES)
    return width, width // d


def _own_lanes(t: int, width: int, lo: int, d: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, width), 1)
    return (lane >= lo) & (lane < lo + d)


def _merge(acc, new, own):
    return new if acc is None else jnp.where(own, new, acc)


def _fwd_kernel(qkv_ref, o_ref, lse_ref, *, heads, d, scale):
    """One image: qkv (1, T, 3·H·D) -> o (1, T, H·D), lse (1, H, T) f32."""
    hd = heads * d
    t = qkv_ref.shape[1]
    width, per = _tile(d)
    for c, h0 in zip(range(0, hd, width), range(0, heads, per)):
        q = qkv_ref[0, :, c:c + width]
        k = qkv_ref[0, :, hd + c:hd + c + width]
        v = qkv_ref[0, :, 2 * hd + c:2 * hd + c + width]
        out = None
        for hh in range(per):
            own = _own_lanes(t, width, hh * d, d) if per > 1 else None
            kh = k if own is None else jnp.where(own, k, jnp.zeros_like(k))
            st = _dot(kh, q, (1, 1)) * scale            # (keys, queries) f32
            m = jnp.max(st, axis=0, keepdims=True)      # (1, T)
            e = jnp.exp(st - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            p = (e * (1.0 / l)).astype(v.dtype)
            out = _merge(out, _dot(p, v, (0, 0)), own)  # (queries, width)
            lse_ref[0, h0 + hh:h0 + hh + 1, :] = m + jnp.log(l)
        o_ref[0, :, c:c + width] = out.astype(o_ref.dtype)


def _bwd_kernel(qkv_ref, do_ref, lse_ref, dqkv_ref, *, heads, d, scale):
    """One image: qkv, dO (1, T, H·D), lse (1, H, T) -> d(qkv)."""
    hd = heads * d
    t = qkv_ref.shape[1]
    width, per = _tile(d)
    for c, h0 in zip(range(0, hd, width), range(0, heads, per)):
        q = qkv_ref[0, :, c:c + width]
        k = qkv_ref[0, :, hd + c:hd + c + width]
        v = qkv_ref[0, :, 2 * hd + c:2 * hd + c + width]
        do = do_ref[0, :, c:c + width]
        dq = dk = dv = None
        for hh in range(per):
            own = _own_lanes(t, width, hh * d, d) if per > 1 else None
            kh = k if own is None else jnp.where(own, k, jnp.zeros_like(k))
            vh = v if own is None else jnp.where(own, v, jnp.zeros_like(v))
            st = _dot(kh, q, (1, 1)) * scale
            p = jnp.exp(st - lse_ref[0, h0 + hh:h0 + hh + 1, :])
            dp = _dot(vh, do, (1, 1))
            delta = jnp.sum(p * dp, axis=0, keepdims=True)
            # the matmuls' operands in the dtypes `_dkvq_kernel` gives them
            ds = (p * (dp - delta)).astype(q.dtype)
            dv = _merge(dv, _dot(p.astype(do.dtype), do, (1, 0)), own)
            dk = _merge(dk, _dot(ds, q, (1, 0)) * scale, own)
            dq = _merge(dq, _dot(ds, k, (0, 0)) * scale, own)
        for i, g in enumerate((dq, dk, dv)):
            dqkv_ref[0, :, i * hd + c:i * hd + c + width] = g.astype(
                dqkv_ref.dtype)


def _image(shape):
    return pl.BlockSpec((1,) + shape, lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _params(t: int, hd: int, itemsize: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=max(_vmem_bytes(t, hd, itemsize), _VMEM_FLOOR))


# The two calls are jitted so that a model's identical layers share ONE trace
# and ONE Mosaic lowering of the unrolled bodies (a call apiece cost
# vitb16_pool's step 4 s of lowering here and 7 s of `setup_s` on the chip's
# host; the compiler inlines the calls, each keeps its block's op_name).
# `interpret` is an argument so that the cached trace is keyed on it.
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _forward(qkv, heads: int, scale: float, interpret: bool):
    b, t, w = qkv.shape
    hd = w // 3
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, d=hd // heads,
                          scale=scale),
        out_shape=[jax.ShapeDtypeStruct((b, t, hd), qkv.dtype),
                   jax.ShapeDtypeStruct((b, heads, t), jnp.float32)],
        grid=(b,),
        in_specs=[_image((t, w))],
        out_specs=[_image((t, hd)), _image((heads, t))],
        compiler_params=_params(t, hd, qkv.dtype.itemsize),
        interpret=interpret,
        name="attn_rows_fwd",
    )(qkv)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _backward(qkv, do, lse, heads: int, scale: float, interpret: bool):
    b, t, w = qkv.shape
    hd = w // 3
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, d=hd // heads,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        grid=(b,),
        in_specs=[_image((t, w)), _image((t, hd)), _image((heads, t))],
        out_specs=_image((t, w)),
        compiler_params=_params(t, hd, qkv.dtype.itemsize),
        interpret=interpret,
        name="attn_rows_bwd",
    )(qkv, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rows(qkv, heads, scale):
    return _forward(qkv, heads, scale, _interpret())[0]


def _rows_fwd(qkv, heads, scale):
    out, lse = _forward(qkv, heads, scale, _interpret())
    return out, (qkv, lse)


def _rows_bwd(heads, scale, res, g):
    qkv, lse = res
    return (_backward(qkv, g, lse, heads, scale, _interpret()),)


_rows.defvjp(_rows_fwd, _rows_bwd)


def rows_attention(qkv: jnp.ndarray, heads: int,
                   scale: Optional[float] = None, mesh=None,
                   batch_axes: tuple = ()) -> jnp.ndarray:
    """Non-causal self-attention of every head over its whole row.

    qkv: (B, T, 3·H·D), a qkv projection's output as it stands: columns
    [q | k | v], each head-major. Returns (B, T, H·D) in qkv.dtype, the
    output projection's input. Same math as `ops/attention.py::attention`
    on the three (B, T, H, D) views (float32 scores, softmax and
    accumulation; P in the compute dtype). Callers gate on `rows_supported`.

    With `mesh` (more than one device) the call runs inside a `shard_map`
    whose batch dimension shards over `batch_axes` (B must divide them;
    () replicates)."""
    hd = qkv.shape[-1] // 3
    if scale is None:
        scale = (hd // heads) ** -0.5
    if mesh is None:
        return _rows(qkv, heads, scale)
    spec = P(batch_axes if batch_axes else None, None, None)
    return shard_map_unchecked(
        lambda x: _rows(x, heads, scale), mesh=mesh, in_specs=(spec,),
        out_specs=spec)(qkv)
