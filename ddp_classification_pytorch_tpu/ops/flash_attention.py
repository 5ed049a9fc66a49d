"""Pallas TPU flash attention — forward AND backward kernels.

The reference has no attention at all (SURVEY §2.2); this kernel serves the
framework's transformer/long-context extension (models/vit.py,
ops/attention.py). Motivation: dense attention materializes the (T, T) score
matrix in HBM; these kernels stream K/V blocks through VMEM and keep the
softmax statistics on-chip, so BOTH passes read/write only O(T·D) from HBM —
the standard flash-attention memory shape, expressed the Pallas/Mosaic way
(same conventions as ops/pallas_kernels.py, the repo's TPU-proven kernel):

- the forward grids over (batch·heads, rows-of-blocks, cols-of-blocks); the
  LAST grid dimension is sequential on TPU, so accumulators live in VMEM
  scratch across its steps and only one (block, D) tile of the streamed
  operand is resident at a time;
- forward carries online-softmax stats (running max m, normalizer l) as
  (block_q, 128) lane-replicated f32 tiles and additionally writes the
  per-row logsumexp (the flash residual) as a (bh, T, 1) f32 array;
- backward is ONE kernel (`flash_dkvq`): it holds a q block fixed and
  streams K/V past it (from the last block down, so that a causal row's dead
  steps come first and every fetch hides under a live tile), rebuilds the
  (bq, bk) score tile from Q·Kᵀ, P = exp(S − lse), dP = dO·Vᵀ and
  dS = P ⊙ (dP − Δ) ONCE a live tile, and feeds all of dQ (a (bq, D)
  scratch), dK and dV from them. dK and dV (and dK_r) stay in VMEM for the
  WHOLE T of a KV head, f32, across the query heads of its group, so nothing
  partial ever goes to HBM and no (T, T) tensor exists there either. That is
  T·(D + Dv)·8 bytes beside the tiles (16-24 MiB at 8,192 tokens: the kernel
  asks for its own `vmem_limit_bytes`, computed from its shapes). Where it
  passes `_VMEM_BUDGET` (T of 40 thousand at 128-wide heads) the backward is the
  classic two-kernel split instead: one kernel grids over q-blocks and
  streams K/V to accumulate dQ, the other grids over kv-blocks and streams
  Q/dO to accumulate dK and dV, each rebuilding P and dS, with only (block,
  D) accumulators resident — max sequence length is HBM-bound, not
  VMEM-bound. Shapes choose the path, nothing a caller sets;
  `flash_backward_total{path}` counts it as the caller is traced. The
  softmax-gradient row term Δ = rowsum(dO ⊙ O) is a cheap elementwise XLA op
  outside the kernels;
- every matmul runs on the MXU with f32 accumulation
  (`preferred_element_type`); CPU/tests run the same kernels in interpret
  mode;
- the O(T·D) guarantee holds for token counts the kernels tile cleanly
  (T ≤ 512 or any multiple of 128 — every ViT in models/vit.py); other T
  route to the dense op, which materializes the (T, T) scores in both
  passes (see `_supported`);
- `window` (causal only) keeps keys j with i − window < j ≤ i: tiles that
  lie wholly outside that band are skipped on BOTH sides in every kernel
  (compute by `pl.when`, DMA by clamping the streamed block's index
  to the band), so a window layer costs its band, not the triangle;
- `diffusion` = (L, B) is the mask of block-diffusion training's two
  streams (models/decoder_lm.py): the row is [clean ; noised], 2L positions,
  blk(i) = (i mod L) // B, and a query sees a key iff clean → clean with
  blk(i) ≥ blk(j), noised → clean with blk(i) > blk(j), noised → noised
  with blk(i) = blk(j); clean → noised never. A noised q block has TWO live
  runs of kv blocks (the clean ones before it, and its own), so the forward
  and the fused backward walk a COMPACT kv dimension, L / block + the own
  run's blocks long, whose step s maps to the kv block it visits
  (`_diffusion_step`): the dead quadrant is never a grid step, and the dead
  steps that remain re-request a resident block as the causal ones do. The
  split backward's dK/dV kernel walks every q block of a kv block and
  clamps its fetches to the two live runs. `flash_tiles_total{mask,state}`
  counts, as a call is traced, the (q block, kv block) tiles of its square
  that hold an allowed pair and those that do not, per kernel launched;
- grouped KV heads (H_q = g·H_kv, models/decoder_lm.py): the K/V index maps
  read block `i // g` for query head `i`, and the backward walks the g query
  heads of a KV head in a sequential grid dimension of their own — no
  repeated K/V is ever materialized;
- the value dimension is v's own (it need not be the score dimension), and
  the scores may have a second part (`q_rope`, `k_rope`: latent attention,
  DeepSeek-V2/V3): S = (Q·Kᵀ + Q_r·K_rᵀ)·scale, two matmuls a tile, with
  K_r held by FEWER heads than K (one for all, in the published models) and
  indexed `i // g_r` the same way — no key that repeats K_r per head, no
  value padded to the score dimension. dK_r sums over every query head that
  read it inside the backward's sequential head dimension.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import spans

_LANES = 128
_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _supported(t: int) -> bool:
    """Shapes the kernels tile well: one whole-T block (small/odd T) or an
    exact multiple of the 128-lane tile. Anything else (e.g. prime T above
    512) would degrade to misaligned micro-blocks — the public entry point
    routes those to the dense op instead."""
    return t <= 512 or t % 128 == 0


def _block(t: int, cap: int = 1024) -> int:
    for b in (1024, 512, 256, 128):
        if b <= cap and t % b == 0:
            return b
    assert t <= cap, f"unsupported T={t} reached the kernel (see _supported)"
    return t  # small/odd T: single block (VMEM easily holds it)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _causal_mask(bq: int, bk: int, jq, jk, window=None):
    """(bq, bk) bool, True where query row ≥ key col in GLOBAL indices for
    q-block jq / kv-block jk (block-local iota + block offsets) and, with
    `window`, also col > row − window."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + jq * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
    allowed = rows >= cols
    if window is not None:
        allowed &= cols > rows - window
    return allowed


def _tile_live(bq: int, bk: int, jq, jk, window=None):
    """Whether tile (q-block jq, kv-block jk) holds any allowed pair: its
    first col ≤ its last row and, with `window`, its last col > its first
    row − window."""
    live = jk * bk < (jq + 1) * bq
    if window is not None:
        live &= (jk + 1) * bk - 1 > jq * bq - window
    return live


def _first_kv_block(bq: int, bk: int, jq, window):
    """Lowest live kv-block of q-block jq (0 without a window)."""
    if window is None:
        return 0
    return jnp.maximum(jq * bq - window + 1, 0) // bk


def _last_q_block(bq: int, bk: int, jk, nq: int, window):
    """Highest live q-block of kv-block jk (the last one without a window)."""
    if window is None:
        return nq - 1
    return jnp.minimum(((jk + 1) * bk + window - 2) // bq, nq - 1)


# ---------------------------------------------------------------------------
# the block-diffusion rule: two streams of L positions, blocks of B
# ---------------------------------------------------------------------------

_FAR = 1 << 30


def _diffusion_tile(bq: int, bk: int, jq, jk, diffusion):
    """Tile (q-block jq, kv-block jk) of a [clean ; noised] row under
    `diffusion` = (L, B) → (its first row and first col, each LOCAL to its
    stream, lo, hi): with d = blk(row) − blk(col) a pair is allowed iff
    lo ≤ d ≤ hi — clean → clean d ≥ 0, noised → clean d ≥ 1, noised →
    noised d = 0, clean → noised never (lo past every d). A tile lies in one
    stream (the blocks divide L). Operators only: Python ints, numpy arrays
    and traced scalars alike."""
    half, _ = diffusion
    noised_q, noised_k = (jq >= half // bq) * 1, (jk >= half // bk) * 1
    lo = noised_q * (1 - noised_k) + (1 - noised_q) * noised_k * _FAR
    hi = (1 - noised_k) * _FAR + noised_k * (noised_q - 1)
    return jq * bq - noised_q * half, jk * bk - noised_k * half, lo, hi


def _diffusion_mask(bq: int, bk: int, jq, jk, diffusion):
    """(bq, bk) bool: the allowed pairs of the tile."""
    block = diffusion[1]
    r0, c0, lo, hi = _diffusion_tile(bq, bk, jq, jk, diffusion)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + r0
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + c0
    if block & (block - 1) == 0:    # a shift where B is a power of two
        shift = block.bit_length() - 1
        d = (rows >> shift) - (cols >> shift)
    else:
        d = rows // block - cols // block
    return (d >= lo) & (d <= hi)


def _diffusion_live(bq: int, bk: int, jq, jk, diffusion):
    """Whether the tile holds any allowed pair: its rows' and cols' blocks
    are contiguous, so d takes every value between its extremes."""
    block = diffusion[1]
    r0, c0, lo, hi = _diffusion_tile(bq, bk, jq, jk, diffusion)
    d_max = (r0 + bq - 1) // block - c0 // block
    d_min = r0 // block - (c0 + bk - 1) // block
    return (d_max >= lo) & (d_min <= hi)


def _diffusion_steps(b: int, diffusion) -> int:
    """Length of the compact kv dimension at blocks of `b`: the clean
    stream's blocks and the noised blocks one q block's own run can span."""
    half, block = diffusion
    return half // b + max(block // b, 1)


def _diffusion_step(b: int, jq, s, diffusion):
    """Step `s` of q-block `jq` in the compact kv dimension → (the kv block
    it visits, whether that tile is live). A clean q block's live kv blocks
    are the clean 0..b1; a noised one's the clean 0..b1 (b1 = −1: none) and
    the noised a2..b2 of its own diffusion blocks. Steps run through the
    first run, then the second; the dead ones after them re-request the
    block already resident."""
    half, block = diffusion
    n = half // b
    noised = jq >= n
    r0 = (jq - noised * n) * b
    r1 = r0 + b - 1
    b1 = jnp.minimum(((r1 // block - noised + 1) * block - 1) // b, n - 1)
    a2 = n + (r0 // block) * block // b
    b2 = n + jnp.minimum(((r1 // block + 1) * block - 1) // b, n - 1)
    first = s <= b1
    own = a2 + s - b1 - 1
    live = first | (noised & (own <= b2))
    return jnp.where(first, s, jnp.where(noised, jnp.minimum(own, b2), b1)), live


def _diffusion_q_block(b: int, jk, qq, diffusion):
    """The q block the split backward's dK/dV kernel fetches at step `qq` of
    kv-block `jk`: `qq` clamped to the kv block's live q blocks — of a clean
    one the clean a1..n−1 and the noised n + a2.., of a noised one the
    noised blocks of its own diffusion blocks."""
    half, block = diffusion
    n = half // b
    noised = jk >= n
    c0 = (jk - noised * n) * b
    c1 = c0 + b - 1
    a1 = (c0 // block) * block // b
    a2 = (c0 // block + 1) * block // b       # > n − 1: no noised q block
    clean = jnp.where(qq < n, jnp.maximum(qq, a1),
                      jnp.where(a2 < n, jnp.clip(qq, n + a2, 2 * n - 1), n - 1))
    own = jnp.clip(qq, n + a1,
                   n + jnp.minimum(((c1 // block + 1) * block - 1) // b, n - 1))
    return jnp.where(noised, own, clean)


def _tile_mask(bq, bk, jq, jk, causal, window, diffusion):
    """The tile's (bq, bk) allowed pairs under whichever rule the call has,
    None without one."""
    if diffusion is not None:
        return _diffusion_mask(bq, bk, jq, jk, diffusion)
    return _causal_mask(bq, bk, jq, jk, window) if causal else None


def _count_tiles(bq: int, bk: int, t: int, causal, window, diffusion) -> None:
    """`flash_tiles_total{mask,state}` for one kernel launched over the
    (t / bq, t / bk) tiles of a row's square: those that hold an allowed
    pair, and those a kernel skips (or never walks)."""
    jq, jk = np.arange(t // bq)[:, None], np.arange(t // bk)[None, :]
    if diffusion is not None:
        live = _diffusion_live(bq, bk, jq, jk, diffusion)
    elif causal:
        live = _tile_live(bq, bk, jq, jk, window)
    else:
        live = np.ones((t // bq, t // bk), bool)
    live = int(np.sum(live))
    mask = ("block_diffusion" if diffusion is not None else
            "none" if not causal else "causal" if window is None else "window")
    spans.count("flash_tiles_total", live, mask=mask, state="live")
    spans.count("flash_tiles_total", (t // bq) * (t // bk) - live, mask=mask,
                state="skipped")


def _scores(q, kb, rope, scale):
    """(bq, bk) f32 scaled scores of one tile; `rope` = (q_r tile, k_r tile)
    adds the second part of a latent-attention score before the scale."""
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if rope is not None:
        s = s + jax.lax.dot_general(rope[0], rope[1], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return s * scale


def _flash_kernel(q_ref, k_ref, v_ref, *rest, scale, nk, causal, window=None,
                  rope=False, diffusion=None):
    """One (batch·head, q-block, kv-block) grid step.

    The kv axis is the LAST grid dimension — sequential on TPU — so the
    online-softmax accumulators persist in VMEM scratch across kv steps and
    only one (block_k, D) K/V tile is resident at a time. With `rope` two
    more inputs follow v: the q_r tile and the (shared) k_r tile. Under
    `diffusion` the last dimension is the compact one (`nk` its length) and
    the kv block of a step comes from `_diffusion_step`."""
    if rope:
        qr_ref, kr_ref, *rest = rest
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    jq, kk = pl.program_id(1), pl.program_id(2)
    step = kk
    # Operands stay in their input dtype (bf16 in the default recipe) so the
    # MXU runs at full rate; every accumulation is f32 via
    # preferred_element_type, and the softmax statistics are f32 throughout.
    q = q_ref[0]                                # (bq, D)
    bq = q.shape[0]
    bk = k_ref.shape[1]
    if diffusion is not None:
        kk, live = _diffusion_step(bq, jq, step, diffusion)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full((bq, _LANES), _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros((bq, _LANES), jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _update():
        kb = k_ref[0]                           # (bk, D)
        vb = v_ref[0]                           # (bk, Dv)
        s = _scores(q, kb, (qr_ref[0], kr_ref[0]) if rope else None,
                    scale)                                       # (bq, bk)
        allowed = _tile_mask(bq, bk, jq, kk, causal, window, diffusion)
        if allowed is not None:
            s = jnp.where(allowed, s, _NEG_INF)
        m = m_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)               # (bq, 1)
        m_new = jnp.maximum(m, jnp.broadcast_to(m_cur, (bq, _LANES)))
        corr = jnp.exp(m - m_new)                                # (bq, LANES)
        # Masked entries need no re-zeroing: kv-block 0 (never skipped)
        # contains column 0, causally allowed for every row, so m_new is
        # finite after the first step and exp(−NEG_INF − m) underflows to
        # exactly 0.
        p = jnp.exp(s - m_new[:, :1])                            # (bq, bk)
        if window is not None or diffusion is not None:
            # the first live tile of a window band can hold rows with no
            # allowed column yet (m_new still −1e30, so exp(s − m_new) = 1
            # on masked entries): those have to be zeroed explicitly (so can
            # a noised q block's clean tiles: its first diffusion block sees
            # no clean key at all)
            p = jnp.where(allowed, p, 0.0)
        l_new = l_scr[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), (bq, _LANES))
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (bq, D)
        acc_scr[:] = acc_scr[:] * corr[:, :1] + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    if causal:
        # Skip tiles entirely above the diagonal — roughly halves causal
        # FLOPs. The K/V index maps clamp to the diagonal block for these
        # steps, so the already-resident tile is re-referenced and the DMA
        # is elided too (halved HBM traffic).
        pl.when(_tile_live(bq, bk, jq, kk, window))(_update)
    elif diffusion is not None:
        pl.when(live)(_update)
    else:
        _update()

    @pl.when(step == nk - 1)
    def _write():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l_scr[:, :1])


def _blocks(t: int, diffusion):
    """(bq, bk) of a row of `t` tokens: under `diffusion` the blocks of ONE
    stream, so that no tile straddles the two."""
    b = _block(t if diffusion is None else diffusion[0], cap=512)
    return b, b


def _flash_forward(q3, k3, v3, scale, causal=False, window=None, qr3=None,
                   kr3=None, diffusion=None):
    """q (bh, T, D), k (bh // g, T, D), v (bh // g, T, Dv) → (out (bh, T,
    Dv), lse (bh, T, 1) f32); g query heads share each KV head. `qr3` (bh,
    T, Dr) and `kr3` (bh // g_r, T, Dr): the scores' second part."""
    bh, t, d = q3.shape
    dv = v3.shape[-1]
    group = bh // k3.shape[0]
    rope = qr3 is not None
    # cap 512 matches the backward's VMEM reasoning (`_TILE_VMEM`): at 1024
    # blocks with d=128, the (bq, bk) f32 score+probability tiles (~8 MB)
    # plus operands and double-buffered K/V approach the compiler's default
    # 16 MiB
    bq, bk = _blocks(t, diffusion)
    grid = (bh, t // bq, t // bk)
    _count_tiles(bq, bk, t, causal, window, diffusion)
    if diffusion is not None:
        grid = grid[:2] + (_diffusion_steps(bk, diffusion),)
        kv_block = lambda j, s: _diffusion_step(  # noqa: E731
            bk, j, s, diffusion)[0]
    elif causal:
        # Above-diagonal steps are compute-skipped in the kernel; clamping
        # the fetched kv block to the diagonal makes those steps re-request
        # the resident tile, so their DMA is elided as well (bq == bk by
        # construction of _block).
        kv_block = lambda j, kk: jnp.clip(  # noqa: E731
            kk, _first_kv_block(bq, bk, j, window), j)
    else:
        kv_block = lambda j, kk: kk  # noqa: E731
    kv_idx = lambda i, j, kk: (i // group, kv_block(j, kk), 0)  # noqa: E731
    q_idx = lambda i, j, kk: (i, j, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, bq, d), q_idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, dv), kv_idx, memory_space=pltpu.VMEM),
    ]
    args = (q3, k3, v3)
    if rope:
        dr, rgroup = qr3.shape[-1], bh // kr3.shape[0]
        in_specs += [
            pl.BlockSpec((1, bq, dr), q_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dr),
                         lambda i, j, kk: (i // rgroup, kv_block(j, kk), 0),
                         memory_space=pltpu.VMEM),
        ]
        args += (qr3, kr3)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, nk=grid[2],
                          causal=causal, window=window, rope=rope,
                          diffusion=diffusion),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), q_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_idx, memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),   # normalizer l
            pltpu.VMEM((bq, dv), jnp.float32),       # output accumulator
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*args)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

# What the backward may ask of a core's VMEM (128 MiB on a v5e), and the part
# of it the (block, ·) tiles of one grid step were sized for: the blocks are
# capped at 512 so that the (bq, bk) f32 score / probability / dP / dS tiles,
# the operand tiles and their double buffers stay under the compiler's default
# 16 MiB. The fused kernel asks for that plus its whole-T accumulators.
_VMEM_BUDGET = 96 * 2 ** 20
_TILE_VMEM = 16 * 2 ** 20


def _fused_vmem_bytes(t: int, widths, itemsize: int) -> int:
    """VMEM the fused backward needs for a sequence of `t` tokens whose K, V
    (and K_r) are `widths` wide: an f32 accumulator of the whole T for each
    (lanes padded to 128) and its output block, double-buffered by the
    pipeline, beside one grid step's tiles."""
    lanes = sum(-(-w // _LANES) * _LANES for w in widths)
    return t * lanes * (4 + 2 * itemsize) + _TILE_VMEM


def _kv_widths(k3, v3, kr3):
    return (k3.shape[-1], v3.shape[-1]) + (() if kr3 is None
                                           else (kr3.shape[-1],))


def backward_path(t: int, widths, itemsize: int) -> str:
    """"fused" | "split": which backward a call with `t` tokens and K, V (and
    K_r) of these `widths` takes — a byte count of its shapes against
    `_VMEM_BUDGET`, nothing a caller sets."""
    return ("fused" if _fused_vmem_bytes(t, widths, itemsize) <= _VMEM_BUDGET
            else "split")


def _dot(a, b, contract):
    """a·b over `contract` = (axis of a, axis of b), f32 accumulation."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _p_ds(q, kb, vb, do, lse, dsum, rope, scale, allowed):
    """One tile's P = exp(S − lse) and dS = P ⊙ (dO·Vᵀ − Δ), both (bq, bk) in
    the operands' dtype: everything the gradients' matmuls read."""
    s = _scores(q, kb, rope, scale)
    if allowed is not None:
        # lse is finite, so exp(−NEG_INF − lse) underflows to exactly 0 —
        # masking s alone zeroes P (and thus dS) on forbidden entries.
        s = jnp.where(allowed, s, _NEG_INF)
    p = jnp.exp(s - lse)
    dp = _dot(do, vb, (1, 1))
    return p.astype(do.dtype), (p * (dp - dsum)).astype(q.dtype)


def _dkvq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, *rest,
                 scale, nq, nk, causal, window=None, group=1, heads=1,
                 rope=False, diffusion=None):
    """Grid (outer heads, query head under each, q-block, kv-block from the
    last down), all sequential: stream K/V past a fixed q block, as the
    forward does, and build each live tile's P and dS ONCE for all of
    dQ += dS·K·scale (a (bq, D) scratch, written after the kv sweep),
    dK += dSᵀ·Q·scale and dV += Pᵀ·dO. The last two are kept for the WHOLE T
    in f32 scratch, at rows kk·bk, across the `group` query heads that read
    one KV head, and written once after the last of them.

    Under `diffusion` the last dimension is the compact one (`nk` its
    length, `_diffusion_step` the kv block of a step), walked from the last
    step down as well.

    `outer` walks the KV heads and `heads` = `group`; with `rope` (latent
    attention: q_r, k_r follow Δ; dQ_r, dK_r among the outputs) it walks the
    heads of K_r, dK and dV still close after every `group` of the `heads`
    query heads under each, and dK_r = Σ dSᵀ·Q_r·scale runs on over all."""
    if rope:
        (qr_ref, kr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref,
         dq_scr, dk_acc, dv_acc, dqr_scr, dkr_acc) = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_acc, dv_acc = rest
    gg, jq, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    # the kv blocks are walked from the last to the first (`_backward_fused`)
    kk = nk - 1 - step
    q = q_ref[0]                                # (bq, D) input dtype
    bq = q.shape[0]
    bk = k_ref.shape[1]
    if diffusion is not None:
        kk, live = _diffusion_step(bq, jq, kk, diffusion)
    # this query head's place among those that read its KV head
    place = gg % group
    first = (jq == 0) & (step == 0)
    last = (jq == nq - 1) & (step == nk - 1)

    @pl.when((place == 0) & first)
    def _init_kv():
        dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(step == 0)
    def _init_q():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)
        if rope:
            dqr_scr[:] = jnp.zeros(dqr_scr.shape, jnp.float32)

    if rope:
        @pl.when((gg == 0) & first)
        def _init_rope():
            dkr_acc[:] = jnp.zeros(dkr_acc.shape, jnp.float32)

    def _update():
        kb = k_ref[0]                           # (bk, D)
        do = do_ref[0]                          # (bq, Dv)
        p, ds = _p_ds(q, kb, v_ref[0], do, lse_ref[0], dsum_ref[0],
                      (qr_ref[0], kr_ref[0]) if rope else None, scale,
                      _tile_mask(bq, bk, jq, kk, causal, window, diffusion))
        rows = (slice(None) if nk == 1
                else pl.ds(pl.multiple_of(kk * bk, bk), bk))
        dv_acc[rows, :] += _dot(p, do, (0, 0))                   # (bk, Dv)
        dk_acc[rows, :] += _dot(ds, q, (0, 0)) * scale           # (bk, D)
        dq_scr[:] += _dot(ds, kb, (1, 0)) * scale                # (bq, D)
        if rope:
            dkr_acc[rows, :] += _dot(ds, qr_ref[0], (0, 0)) * scale
            dqr_scr[:] += _dot(ds, kr_ref[0], (1, 0)) * scale

    if causal:
        pl.when(_tile_live(bq, bk, jq, kk, window))(_update)
    elif diffusion is not None:
        pl.when(live)(_update)
    else:
        _update()

    @pl.when(step == nk - 1)
    def _write_q():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        if rope:
            dqr_ref[0] = dqr_scr[:].astype(dqr_ref.dtype)

    @pl.when((place == group - 1) & last)
    def _write_kv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if rope:
        @pl.when((gg == heads - 1) & last)
        def _write_rope():
            dkr_ref[0] = dkr_acc[:].astype(dkr_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, *rest,
               scale, nk, causal, window=None, rope=False, diffusion=None):
    """The split path's first kernel. Grid (bh, q-block, kv-block): stream
    K/V past a fixed q block, accumulating dQ = Σ_k dS·K·scale in VMEM
    scratch (and, with `rope`, dQ_r = Σ_k dS·K_r·scale beside it: inputs
    q_r, k_r follow Δ)."""
    if rope:
        qr_ref, kr_ref, dq_ref, dqr_ref, dq_scr, dqr_scr = rest
    else:
        dq_ref, dq_scr = rest
    jq, kk = pl.program_id(1), pl.program_id(2)
    step = kk
    q = q_ref[0]                                # (bq, D) input dtype
    bq = q.shape[0]
    bk = k_ref.shape[1]
    if diffusion is not None:   # the forward's compact kv dimension
        kk, live = _diffusion_step(bq, jq, step, diffusion)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)
        if rope:
            dqr_scr[:] = jnp.zeros(dqr_scr.shape, jnp.float32)

    def _update():
        kb = k_ref[0]                           # (bk, D)
        _, ds = _p_ds(q, kb, v_ref[0], do_ref[0], lse_ref[0], dsum_ref[0],
                      (qr_ref[0], kr_ref[0]) if rope else None, scale,
                      _tile_mask(bq, bk, jq, kk, causal, window, diffusion))
        dq_scr[:] += _dot(ds, kb, (1, 0)) * scale
        if rope:
            dqr_scr[:] += _dot(ds, kr_ref[0], (1, 0)) * scale

    if causal:
        pl.when(_tile_live(bq, bk, jq, kk, window))(_update)
    elif diffusion is not None:
        pl.when(live)(_update)
    else:
        _update()

    @pl.when(step == nk - 1)
    def _write():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        if rope:
            dqr_ref[0] = dqr_scr[:].astype(dqr_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dsum_ref, *rest,
                scale, nq, causal, window=None, group=1, heads=None,
                diffusion=None):
    """The split path's second kernel. Grid (kv heads, kv-block, query head
    of the group, q-block): stream the Q/dO of every query head that reads
    this KV head past a fixed kv block, accumulating dK = Σ_q dSᵀ·Q·scale and
    dV = Σ_q Pᵀ·dO in VMEM scratch (both trailing grid dimensions are
    sequential).

    With `heads` (latent attention: inputs k_r, q_r follow Δ) the first grid
    dimension walks the heads of K_r and the third the `heads` query heads
    that read each: dK and dV still close after every `group` of them, and
    dK_r = Σ_q dSᵀ·Q_r·scale runs on over all."""
    rope = heads is not None
    if rope:
        kr_ref, qr_ref, dk_ref, dv_ref, dkr_ref, dk_scr, dv_scr, dkr_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    jk, gg, qq = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    kb = k_ref[0]                               # (bk, D) input dtype
    bk = kb.shape[0]
    bq = q_ref.shape[1]
    # this KV head's place among the query heads that read it
    place = gg % group if rope else gg

    @pl.when((place == 0) & (qq == 0))
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    if rope:
        @pl.when((gg == 0) & (qq == 0))
        def _init_rope():
            dkr_scr[:] = jnp.zeros(dkr_scr.shape, jnp.float32)

    def _update():
        q = q_ref[0]                            # (bq, D)
        do = do_ref[0]                          # (bq, Dv)
        # q-block index is the LAST grid dim here; kv-block is dim 1
        p, ds = _p_ds(q, kb, v_ref[0], do, lse_ref[0], dsum_ref[0],
                      (qr_ref[0], kr_ref[0]) if rope else None, scale,
                      _tile_mask(bq, bk, qq, jk, causal, window, diffusion))
        dv_scr[:] += _dot(p, do, (0, 0))                         # (bk, Dv)
        dk_scr[:] += _dot(ds, q, (0, 0)) * scale
        if rope:
            dkr_scr[:] += _dot(ds, qr_ref[0], (0, 0)) * scale

    if causal:
        pl.when(_tile_live(bq, bk, qq, jk, window))(_update)
    elif diffusion is not None:
        pl.when(_diffusion_live(bq, bk, qq, jk, diffusion))(_update)
    else:
        _update()

    @pl.when((place == group - 1) & (qq == nq - 1))
    def _write():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if rope:
        @pl.when((gg == heads - 1) & (qq == nq - 1))
        def _write_rope():
            dkr_ref[0] = dkr_scr[:].astype(dkr_ref.dtype)


def _vmem(shape, index):
    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _flash_backward_impl(q3, k3, v3, do3, lse, dsum, scale, causal=False,
                         window=None, qr3=None, kr3=None, diffusion=None):
    """(bh, T, D) q, (bh, T, Dv) dO, (bh // g, T, D | Dv) k | v + (bh, T, 1)
    lse/Δ → (dq, dk, dv), O(T·D) HBM; with the scores' second part (`qr3`
    (bh, T, Dr), `kr3` (bh // g_r, T, Dr)) → (dq, dk, dv, dq_r, dk_r).

    One fused kernel wherever its whole-T accumulators fit `_VMEM_BUDGET`
    (`_fused_vmem_bytes`: T of 40 thousand at 128-wide K and V in bf16), the
    two-kernel split beyond. The choice is made from the shapes as the
    caller is traced, and counted there: `flash_backward_total{path}`."""
    path = backward_path(q3.shape[1], _kv_widths(k3, v3, kr3),
                         q3.dtype.itemsize)
    spans.count("flash_backward_total", path=path)
    impl = _backward_fused if path == "fused" else _backward_split
    return impl(q3, k3, v3, do3, lse, dsum, scale, causal, window, qr3, kr3,
                diffusion)


def _band_blocks(causal, bq, bk, nq, window, diffusion=None):
    """(kv block fetched at step kk of q-block j, q block fetched at step qq
    of kv-block j): the same DMA-elision trick as the forward — a
    compute-skipped step re-requests a block of the band (bq == bk by
    construction of _block), so nothing is fetched for it."""
    if diffusion is not None:   # the first over the compact kv dimension
        return (lambda j, s: _diffusion_step(bk, j, s, diffusion)[0],
                lambda j, qq: _diffusion_q_block(bq, j, qq, diffusion))
    if not causal:
        return (lambda j, kk: kk), (lambda j, qq: qq)
    return (lambda j, kk: jnp.clip(kk, _first_kv_block(bq, bk, j, window), j),
            lambda j, qq: jnp.clip(qq, j, _last_q_block(bq, bk, j, nq, window)))


def _backward_fused(q3, k3, v3, do3, lse, dsum, scale, causal, window, qr3,
                    kr3, diffusion=None):
    bh, t, d = q3.shape
    dv = v3.shape[-1]
    group = bh // k3.shape[0]
    rope = qr3 is not None
    bq, bk = _blocks(t, diffusion)
    nq, nk = t // bq, t // bk
    _count_tiles(bq, bk, t, causal, window, diffusion)
    if diffusion is not None:   # kv steps, not kv blocks, from here on
        nk = _diffusion_steps(bk, diffusion)
    # the first grid dimension and the query heads under each of its entries:
    # the KV heads and their group, or K_r's heads and theirs
    outer = kr3.shape[0] if rope else k3.shape[0]
    heads = bh // outer
    # The kv blocks of a q block are walked from the LAST to the first: a
    # causal row's dead steps (blocks past the diagonal, a fraction of a
    # microsecond each) then come first and re-request the diagonal block,
    # and the row ends on a live tile, under which the pipeline fetches the
    # next row's q, dO and diagonal K/V. Walked upwards the row ends on dead
    # steps, too short to hide that fetch: 5-6 % of the kernel at 8,192
    # tokens (PERF.md §6, PR 36). dQ's sum runs over the blocks in that order.
    band_block, _ = _band_blocks(causal, bq, bk, nq, window, diffusion)
    kv_block = lambda j, step: band_block(j, nk - 1 - step)  # noqa: E731
    q_idx = lambda i, g, j, kk: (i * heads + g, j, 0)  # noqa: E731
    kv_idx = lambda i, g, j, kk: (  # noqa: E731
        (i * heads + g) // group, kv_block(j, kk), 0)
    kv_whole = lambda i, g, j, kk: ((i * heads + g) // group, 0, 0)  # noqa: E731

    in_specs = [_vmem((1, bq, d), q_idx), _vmem((1, bk, d), kv_idx),
                _vmem((1, bk, dv), kv_idx), _vmem((1, bq, dv), q_idx),
                _vmem((1, bq, 1), q_idx), _vmem((1, bq, 1), q_idx)]
    args = (q3, k3, v3, do3, lse, dsum)
    out_shape = [jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                 jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                 jax.ShapeDtypeStruct(v3.shape, v3.dtype)]
    out_specs = [_vmem((1, bq, d), q_idx), _vmem((1, t, d), kv_whole),
                 _vmem((1, t, dv), kv_whole)]
    scratch = [pltpu.VMEM((bq, d), jnp.float32),
               pltpu.VMEM((t, d), jnp.float32),
               pltpu.VMEM((t, dv), jnp.float32)]
    if rope:
        dr = qr3.shape[-1]
        in_specs += [_vmem((1, bq, dr), q_idx),
                     _vmem((1, bk, dr),
                           lambda i, g, j, kk: (i, kv_block(j, kk), 0))]
        args += (qr3, kr3)
        out_shape += [jax.ShapeDtypeStruct(qr3.shape, qr3.dtype),
                      jax.ShapeDtypeStruct(kr3.shape, kr3.dtype)]
        out_specs += [_vmem((1, bq, dr), q_idx),
                      _vmem((1, t, dr), lambda i, g, j, kk: (i, 0, 0))]
        scratch += [pltpu.VMEM((bq, dr), jnp.float32),
                    pltpu.VMEM((t, dr), jnp.float32)]
    return tuple(pl.pallas_call(
        functools.partial(_dkvq_kernel, scale=scale, nq=nq, nk=nk,
                          causal=causal, window=window, group=group,
                          heads=heads, rope=rope, diffusion=diffusion),
        out_shape=out_shape,
        grid=(outer, heads, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_fused_vmem_bytes(
                t, _kv_widths(k3, v3, kr3), q3.dtype.itemsize)),
        interpret=_interpret(),
        name="flash_dkvq",
    )(*args))


def _backward_split(q3, k3, v3, do3, lse, dsum, scale, causal, window, qr3,
                    kr3, diffusion=None):
    """The path beyond the fused kernel's VMEM: one kernel grids over
    q-blocks and streams K/V to accumulate dQ, the other grids over kv-blocks
    and streams Q/dO to accumulate dK and dV, each rebuilding the tile's P
    and dS, so that only (block, D) accumulators are resident and the
    sequence length is bound by HBM alone."""
    bh, t, d = q3.shape
    dv = v3.shape[-1]
    bh_kv = k3.shape[0]
    group = bh // bh_kv
    rope = qr3 is not None
    bq, bk = _blocks(t, diffusion)
    nq, nk = t // bq, t // bk
    for _ in range(2):   # two kernels walk the square
        _count_tiles(bq, bk, t, causal, window, diffusion)
    # the dQ kernel's kv dimension: the forward's (compact under `diffusion`)
    kv_steps = nk if diffusion is None else _diffusion_steps(bk, diffusion)
    # the dK/dV kernel's first grid dimension and the query heads under each
    # of its entries: the KV heads and their group, or K_r's heads and theirs
    outer = kr3.shape[0] if rope else bh_kv
    heads = bh // outer
    kv_block, q_block = _band_blocks(causal, bq, bk, nq, window, diffusion)
    kv_idx = lambda i, j, kk: (i // group, kv_block(j, kk), 0)  # noqa: E731
    q_row_idx = lambda i, j, g, qq: (  # noqa: E731
        i * heads + g, q_block(j, qq), 0)
    q_idx = lambda i, j, kk: (i, j, 0)  # noqa: E731

    in_specs = [_vmem((1, bq, d), q_idx), _vmem((1, bk, d), kv_idx),
                _vmem((1, bk, dv), kv_idx), _vmem((1, bq, dv), q_idx),
                _vmem((1, bq, 1), q_idx), _vmem((1, bq, 1), q_idx)]
    args = (q3, k3, v3, do3, lse, dsum)
    out_shape = jax.ShapeDtypeStruct((bh, t, d), q3.dtype)
    out_specs = _vmem((1, bq, d), q_idx)
    scratch = [pltpu.VMEM((bq, d), jnp.float32)]
    if rope:
        dr = qr3.shape[-1]
        in_specs += [_vmem((1, bq, dr), q_idx),
                     _vmem((1, bk, dr),
                           lambda i, j, kk: (i // heads, kv_block(j, kk), 0))]
        args += (qr3, kr3)
        out_shape = [out_shape, jax.ShapeDtypeStruct((bh, t, dr), qr3.dtype)]
        out_specs = [out_specs, _vmem((1, bq, dr), q_idx)]
        scratch.append(pltpu.VMEM((bq, dr), jnp.float32))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, nk=kv_steps, causal=causal,
                          window=window, rope=rope, diffusion=diffusion),
        out_shape=out_shape,
        grid=(bh, nq, kv_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="flash_dq",
    )(*args)

    if rope:
        # the KV head of query head g under K_r's head i, and its block
        kv_own = lambda i, j, g, qq: ((i * heads + g) // group, j, 0)  # noqa: E731
    else:
        kv_own = lambda i, j, g, qq: (i, j, 0)  # noqa: E731
    in_specs = [_vmem((1, bk, d), kv_own), _vmem((1, bk, dv), kv_own),
                _vmem((1, bq, d), q_row_idx), _vmem((1, bq, dv), q_row_idx),
                _vmem((1, bq, 1), q_row_idx), _vmem((1, bq, 1), q_row_idx)]
    args = (k3, v3, q3, do3, lse, dsum)
    out_shape = [jax.ShapeDtypeStruct((bh_kv, t, d), k3.dtype),
                 jax.ShapeDtypeStruct((bh_kv, t, dv), v3.dtype)]
    out_specs = [_vmem((1, bk, d), kv_own), _vmem((1, bk, dv), kv_own)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, dv), jnp.float32)]
    if rope:
        kr_own = lambda i, j, g, qq: (i, j, 0)  # noqa: E731
        in_specs += [_vmem((1, bk, dr), kr_own), _vmem((1, bq, dr), q_row_idx)]
        args += (kr3, qr3)
        out_shape.append(jax.ShapeDtypeStruct(kr3.shape, kr3.dtype))
        out_specs.append(_vmem((1, bk, dr), kr_own))
        scratch.append(pltpu.VMEM((bk, dr), jnp.float32))
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, nq=nq, causal=causal,
                          window=window, group=group,
                          heads=heads if rope else None, diffusion=diffusion),
        out_shape=out_shape,
        grid=(outer, nk, heads, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="flash_dkv",
    )(*args)
    if rope:
        return dq[0], dkv[0], dkv[1], dq[1], dkv[2]
    return dq, dkv[0], dkv[1]


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def _to3(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _to4(x3, b, h):
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: Optional[float] = None,
                    causal: bool = False,
                    window: Optional[int] = None,
                    q_rope: Optional[jnp.ndarray] = None,
                    k_rope: Optional[jnp.ndarray] = None,
                    diffusion_block: Optional[int] = None) -> jnp.ndarray:
    """Scaled-dot-product attention, (B, T, H, D) → (B, T, H, Dv), optionally
    causal (row i attends keys ≤ i, matching ops/attention.py::attention)
    and, with `window`, banded (keys i − window < j ≤ i). k/v may carry
    fewer heads than q (H_q a multiple of H_kv: grouped-query attention),
    and v its own last dimension Dv.

    `q_rope` (B, T, H, Dr) and `k_rope` (B, T, H_r, Dr), H_kv a multiple of
    H_r, add q_rope·k_ropeᵀ to the scores (latent attention: the rotary part
    of the key is one head that every query head reads); the default scale is
    then (D + Dr)^-½.

    `diffusion_block` = B (not with `causal`): the row is block-diffusion
    training's two streams, [clean ; noised] of T / 2 positions each, under
    the mask of ops/attention.py::diffusion_mask (the module docstring).

    Forward and backward are both Pallas streaming kernels: O(T·D) HBM
    traffic, no (T, T) tensor materialized in either pass. Token counts
    the kernels cannot tile cleanly (see `_supported`) fall back to the
    framework's dense op — same math, same signature.
    """
    if (k.shape[:3] != v.shape[:3] or q.shape[:2] != k.shape[:2]
            or q.shape[3] != k.shape[3] or q.shape[2] % k.shape[2]):
        # Self-attention kernel: one T for q and kv. Without this check a
        # shorter k/v would silently read clamped (repeated) tail blocks.
        raise ValueError(
            f"flash_attention requires q/k/v of equal shape (or k/v with a "
            f"divisor of q's heads, v with its own last dimension), got "
            f"{q.shape}/{k.shape}/{v.shape}")
    if (q_rope is None) != (k_rope is None):
        raise ValueError("flash_attention: q_rope and k_rope come together")
    if q_rope is not None and (
            q_rope.shape[:3] != q.shape[:3] or k_rope.shape[:2] != k.shape[:2]
            or k_rope.shape[3] != q_rope.shape[3]
            or k.shape[2] % k_rope.shape[2]):
        raise ValueError(
            f"flash_attention: q_rope {q_rope.shape} / k_rope {k_rope.shape} "
            f"do not go with q {q.shape} / k {k.shape}")
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if window is not None and window >= q.shape[1]:
        window = None  # the band is the whole triangle
    diffusion = None
    if diffusion_block is not None:
        if causal or q.shape[1] % (2 * diffusion_block):
            raise ValueError(
                f"flash_attention: diffusion_block {diffusion_block} takes a "
                f"row of two streams of whole blocks and no causal mask, got "
                f"T={q.shape[1]}, causal={causal}")
        diffusion = (q.shape[1] // 2, diffusion_block)
    if not diffusion_supported(q.shape[1], diffusion_block):
        from .attention import attention

        # a shape rule, not an error — but never a silent one: the caller
        # asked for the kernel and is getting the (T, T)-materializing op
        warnings.warn(
            f"flash_attention: T={q.shape[1]} is not kernel-tileable "
            "(need T <= 512 or a multiple of 128); falling through to the "
            "dense op ops.attention.attention", stacklevel=2)
        return attention(q, k, v, causal=causal, scale=scale, window=window,
                         q_rope=q_rope, k_rope=k_rope,
                         diffusion_block=diffusion_block)
    if q_rope is not None:
        return _flash_rope(q, k, v, q_rope, k_rope, scale, causal, window,
                           diffusion)
    return _flash(q, k, v, scale, causal, window, diffusion)


def diffusion_supported(t: int, diffusion_block: Optional[int]) -> bool:
    """Whether the kernels take a row of `t` tokens: `_supported`, and under
    `diffusion_block` = B of ONE stream's t / 2 tokens, with B and the
    stream's kernel block whole multiples one of the other (a tile then lies
    in whole diffusion blocks, or a diffusion block in whole tiles)."""
    if diffusion_block is None:
        return _supported(t)
    half = t // 2
    if not _supported(half):
        return False
    b = _block(half, cap=512)
    return b % diffusion_block == 0 or diffusion_block % b == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, window=None, diffusion=None):
    return _fa_fwd(q, k, v, scale, causal, window, diffusion)[0]


def _fa_fwd(q, k, v, scale, causal, window=None, diffusion=None, q_rope=None,
            k_rope=None):
    rope = () if q_rope is None else (_to3(q_rope), _to3(k_rope))
    s = scale if scale is not None else (
        q.shape[-1] + (rope[0].shape[-1] if rope else 0)) ** -0.5
    b, _, h, _ = q.shape
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    out3, lse = _flash_forward(q3, k3, v3, s, causal, window, *rope,
                               diffusion=diffusion)
    # names for a rematerialization policy: a caller that saves these two
    # (`jax.checkpoint_policies.save_only_these_names`) does not run the
    # forward kernel again in its backward pass
    out3 = checkpoint_name(out3, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # Residuals keep the 3D views the backward kernels consume directly —
    # saving the 4D originals instead would re-pay three transpose passes.
    return _to4(out3, b, h), (q3, k3, v3, out3, lse) + rope


def _fa_bwd(scale, causal, window, diffusion, res, g):
    q3, k3, v3, out3, lse, *rope = res
    # Re-resolve from the static nondiff arg: the kernels bake `scale` into
    # their compiled body, so it must stay a Python float, not a residual
    # array.
    s = scale if scale is not None else (
        q3.shape[-1] + (rope[0].shape[-1] if rope else 0)) ** -0.5
    b, _, h, _ = g.shape  # cotangent carries the static 4D layout
    do3 = _to3(g)
    # Softmax-gradient row term Δ = rowsum(dO ⊙ O): one elementwise pass,
    # f32, shaped like lse so the kernels read it as a (bq, 1) tile.
    dsum = jnp.sum(do3.astype(jnp.float32) * out3.astype(jnp.float32),
                   axis=-1, keepdims=True)
    dq3, dk3, dv3, *drope = _flash_backward_impl(
        q3, k3, v3, do3, lse, dsum, s, causal, window, *rope,
        diffusion=diffusion)
    h_kv = k3.shape[0] // b
    out = (_to4(dq3, b, h), _to4(dk3, b, h_kv), _to4(dv3, b, h_kv))
    if rope:
        out += (_to4(drope[0], b, h), _to4(drope[1], b, rope[1].shape[0] // b))
    return out


_flash.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_rope(q, k, v, q_rope, k_rope, scale, causal, window=None,
                diffusion=None):
    return _fa_fwd(q, k, v, scale, causal, window, diffusion, q_rope, k_rope)[0]


_flash_rope.defvjp(
    lambda q, k, v, q_rope, k_rope, scale, causal, window, diffusion:
    _fa_fwd(q, k, v, scale, causal, window, diffusion, q_rope, k_rope), _fa_bwd)


# ---------------------------------------------------------------------------
# (out, lse) variant — building block for ring/blockwise composition
# ---------------------------------------------------------------------------

def flash_attention_with_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             scale: Optional[float] = None,
                             causal: bool = False):
    """Like `flash_attention` but also returns the per-row logsumexp of the
    scaled scores as (B, H, T) f32 — exactly the statistic needed to merge
    partial attention over KV blocks held on other devices (ring attention,
    ops/attention.py). Both outputs are differentiable: an lse cotangent
    folds into the kernels' Δ term (dS = P ⊙ (dP − (Δ − ḡ_lse))), so the
    merged result backpropagates exactly.

    Requires a kernel-supported T (see `_supported`); callers gate on that.
    """
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(
            f"flash_attention_with_lse requires q/k/v of equal shape, got "
            f"{q.shape}/{k.shape}/{v.shape}")
    if not _supported(q.shape[1]):
        raise ValueError(
            f"T={q.shape[1]} is not kernel-tileable (need T ≤ 512 or a "
            "multiple of 128)")
    return _flash_lse(q, k, v, scale, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_lse(q, k, v, scale, causal):
    return _fl_fwd(q, k, v, scale, causal)[0]


def _fl_fwd(q, k, v, scale, causal):
    out, res = _fa_fwd(q, k, v, scale, causal)
    b, _, h, _ = q.shape
    lse = res[4]  # (bh, T, 1) f32
    return (out, lse[:, :, 0].reshape(b, h, -1)), res


def _fl_bwd(scale, causal, res, g):
    g_out, g_lse = g
    q3, k3, v3, out3, lse = res
    s = scale if scale is not None else q3.shape[-1] ** -0.5
    b, _, h, _ = g_out.shape
    do3 = _to3(g_out)
    # lse cotangent: dlse/dS = P, so dS = P ⊙ (dP − Δ) + P·ḡ_lse
    #              = P ⊙ (dP − (Δ − ḡ_lse)) — fold ḡ_lse into the Δ input.
    dsum = jnp.sum(do3.astype(jnp.float32) * out3.astype(jnp.float32),
                   axis=-1, keepdims=True)
    dsum = dsum - g_lse.astype(jnp.float32).reshape(b * h, -1)[:, :, None]
    dq3, dk3, dv3 = _flash_backward_impl(q3, k3, v3, do3, lse, dsum, s,
                                         causal)
    return (_to4(dq3, b, h), _to4(dk3, b, h), _to4(dv3, b, h))


_flash_lse.defvjp(_fl_fwd, _fl_bwd)
