"""Attention ops — dense single-device and ring (sequence-parallel) variants.

The reference is all-CNN: no attention, no sequence axis anywhere in its tree
(SURVEY §2.2 — `NESTED/model/*.py`, backbones at `BASELINE/main.py:134-144`),
so its only "big dimension" is the class dim, which this framework already
shards over the mesh `model` axis (parallel/mesh.py). This module supplies the
genuine long-context mechanism on top of that: exact ring attention, so the
transformer backbone family (models/vit.py) can shard the TOKEN axis across
chips and scale sequence length past one chip's HBM.

How it works (TPU-first, not a translation of any GPU kernel):

- Q/K/V are sharded on the sequence axis over a mesh axis. Each device holds
  (B, T/N, H, D) shards.
- Every device computes blockwise attention of its Q shard against the KV
  shard it currently holds, then passes the KV shard to its ring neighbor via
  `jax.lax.ppermute` — N steps visit every KV block. The permute rides ICI
  neighbor links; XLA overlaps the transfer with the current block's compute.
- Softmax is accumulated online across blocks with the usual running
  (max m, normalizer l, output o) rescaling, in f32, so the result is EXACT
  dense attention — same FLOPs, O(T/N) activation memory per device.
- Static control flow (`lax.fori_loop` over a compile-time ring size), static
  shapes, MXU-shaped einsums with f32 accumulation via
  `preferred_element_type`.

`ring_attention` degrades to the dense op when the mesh axis is absent or has
size 1, so model code calls one function unconditionally.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.compat import shard_map_unchecked

# Finite stand-in for -inf: keeps exp()/max() arithmetic NaN-free when a
# whole block is masked out (causal ring steps where the visiting KV block
# lies entirely in the query's future).
_NEG_INF = -1e30


def diffusion_mask(t: int, block: int) -> jnp.ndarray:
    """(t, t) bool, True = query row sees key col: block-diffusion training's
    two streams in one row, [clean ; noised] of L = t / 2 positions each
    (arXiv:2503.09573). With blk(i) = (i mod L) // block: clean → clean iff
    blk(i) ≥ blk(j) (block-causal, both ways inside a block); noised → clean
    iff blk(i) > blk(j) (the clean text of strictly earlier blocks); noised →
    noised iff blk(i) = blk(j) (its own block); clean → noised never."""
    half = t // 2
    at = jnp.arange(t)
    noised, blk = at >= half, (at % half) // block
    d = blk[:, None] - blk[None, :]
    rows, cols = noised[:, None], noised[None, :]
    return jnp.where(cols, rows & (d == 0), jnp.where(rows, d >= 1, d >= 0))


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    q_rope: Optional[jnp.ndarray] = None,
    k_rope: Optional[jnp.ndarray] = None,
    diffusion_block: Optional[int] = None,
) -> jnp.ndarray:
    """Dense scaled-dot-product attention.

    q: (B, T, H, D); k: (B, T, H_kv, D), v: (B, T, H_kv, Dv) with H a
    multiple of H_kv (query head h reads KV head h // (H / H_kv); no repeated
    K/V is built). Returns (B, T, H, Dv) in q.dtype. Softmax in f32. `window`
    (causal only) keeps keys j with i − window < j ≤ i. `q_rope` (B, T, H,
    Dr) / `k_rope` (B, T, H_r, Dr) are a second part of the scores (latent
    attention): here they are simply joined to q and k, `k_rope` repeated to
    k's heads — the small-T op may; the kernels do not. `diffusion_block`
    (not with `causal`): the row is two streams under `diffusion_mask`.
    """
    if q_rope is not None:
        k_rope = jnp.repeat(k_rope, k.shape[2] // k_rope.shape[2], axis=2)
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([k, k_rope], axis=-1)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("attention: a window needs causal=True")
    if diffusion_block is not None and causal:
        raise ValueError("attention: diffusion_block is a mask of its own, "
                         "not a causal one")
    b, t, h, d = q.shape
    grouped = k.shape[2] != h
    if grouped:
        q = q.reshape(b, t, k.shape[2], h // k.shape[2], d)
    qk, pv = (("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd") if grouped
              else ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"))
    s = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rows, cols = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
        mask = cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, _NEG_INF)
    elif diffusion_block is not None:
        s = jnp.where(diffusion_mask(t, diffusion_block), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(pv, p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if grouped:
        out = out.reshape(b, t, h, v.shape[-1])
    return out.astype(q.dtype)


def _block_update(q, k, v, m, l, o, scale, mask=None):
    """One online-softmax accumulation step against a KV block.

    q: (B,Tq,H,D); k,v: (B,Tk,H,D); m,l: (B,H,Tq) f32; o: (B,Tq,H,D) f32.
    mask: optional (Tq, Tk) bool, True = attend.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)                      # (B,H,Tq)
    p = jnp.exp(s - m_new[..., None])              # f32 (B,H,Tq,Tk)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_shard(q, k, v, *, axis_name: str, axis_size: int, causal: bool,
                scale: float):
    """Per-shard body (inside shard_map): N-step ring over KV shards."""
    b, t_local, h, d = q.shape
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    m = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    o = jnp.zeros((b, t_local, h, d), jnp.float32)

    def mask_for(step):
        if not causal:
            return None
        # After `step` permutes, the KV block this rank holds originated at
        # rank (rank - step) mod N; global token positions decide the causal
        # mask exactly as in the dense op.
        src = (rank - step) % axis_size
        q_pos = rank * t_local + jnp.arange(t_local)
        k_pos = src * t_local + jnp.arange(t_local)
        return k_pos[None, :] <= q_pos[:, None]

    def body(step, carry):
        kb, vb, m, l, o = carry
        m, l, o = _block_update(q, kb, vb, m, l, o, scale, mask_for(step))
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return kb, vb, m, l, o

    # N-1 update+rotate rounds, then the last visiting block updates outside
    # the loop — no wasted final ppermute pair (the rotated shards would be
    # discarded, but a collective inside the loop body cannot be DCE'd).
    kb, vb, m, l, o = jax.lax.fori_loop(0, axis_size - 1, body, (k, v, m, l, o))
    m, l, o = _block_update(q, kb, vb, m, l, o, scale, mask_for(axis_size - 1))
    # Rows with l == 0 cannot occur: step 0 processes the local (diagonal)
    # block, whose self position is always unmasked.
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def flash_supported(t_local: int) -> bool:
    """Whether the Pallas flash kernel tiles a (local) sequence length
    cleanly (lazy import: flash_attention imports this module for its dense
    fallback)."""
    from .flash_attention import _supported

    return _supported(t_local)


def _ring_shard_flash(q, k, v, *, axis_name: str, axis_size: int,
                      causal: bool, scale: float):
    """Flash-kernel ring body: each visiting KV shard is consumed by the
    Pallas streaming kernel (ops/flash_attention.py), whose (out, lse) pair
    is exactly the statistic needed to merge visits — so the per-device
    score tile never materializes even locally. Step 0 is the resident
    (diagonal) shard, statically known, so the causal case runs the causal
    kernel there and a two-way past/future `lax.cond` on later visits
    (per-device runtime branch; no collectives inside, so SPMD-safe)."""
    from .flash_attention import flash_attention_with_lse

    b, t_local, h, d = q.shape
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o0, lse0 = flash_attention_with_lse(q, k, v, scale=scale, causal=causal)
    m = lse0                                       # (B, H, Tl) f32, finite
    l = jnp.ones_like(lse0)                        # each visit is normalized
    o = o0.astype(jnp.float32)

    def visit_full(q, kb, vb):
        out, lse = flash_attention_with_lse(q, kb, vb, scale=scale)
        return out.astype(jnp.float32), lse

    def visit_future(q, kb, vb):
        # entirely in the query's future: contributes nothing; _NEG_INF (not
        # -inf) keeps exp(lse − m) = 0 without inf−inf NaNs in the merge
        return (jnp.zeros((b, t_local, h, d), jnp.float32),
                jnp.full((b, h, t_local), _NEG_INF, jnp.float32))

    def body(step, carry):
        kb, vb, m, l, o = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        src = (rank - step) % axis_size            # origin of this KV shard
        if causal:
            o_i, lse_i = jax.lax.cond(src < rank, visit_full, visit_future,
                                      q, kb, vb)
        else:
            o_i, lse_i = visit_full(q, kb, vb)
        m_new = jnp.maximum(m, lse_i)
        c_run = jnp.exp(m - m_new)                 # (B, H, Tl)
        c_vis = jnp.exp(lse_i - m_new)
        l = l * c_run + c_vis
        o = (o * c_run.transpose(0, 2, 1)[..., None]
             + o_i * c_vis.transpose(0, 2, 1)[..., None])
        return kb, vb, m_new, l, o

    _, _, m, l, o = jax.lax.fori_loop(1, axis_size, body, (k, v, m, l, o))
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def batch_axes(mesh: Mesh, axis_name: Optional[str], rows: int) -> tuple:
    """The mesh axes a per-row attention body shards its batch over: every
    OTHER >1 mesh axis (the 'data' axis in this framework's meshes) — the
    body is batch-local, and leaving the batch unsharded would replicate the
    full global batch's attention onto every device, axis_size× redundant
    FLOPs/memory in the O(T²) hot path. () when `rows` doesn't divide those
    axes (e.g. the 2-sample dummy batch of model.init) — correctness never
    depends on it."""
    axes = tuple(
        a for a in mesh.axis_names if a != axis_name and mesh.shape[a] > 1)
    if axes and rows % functools.reduce(
            lambda s, a: s * mesh.shape[a], axes, 1):
        return ()
    return axes


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Optional[Mesh] = None,
    axis_name: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Exact attention with the sequence axis sharded over `axis_name`.

    q, k, v: (B, T, H, D) with T divisible by the axis size. Falls back to
    the dense op when no mesh/axis is given or the axis has size 1 — model
    code calls this unconditionally and the single-chip path stays a single
    fused XLA computation. `use_flash` consumes each visiting KV shard with
    the Pallas streaming kernel instead of the blockwise einsum (requires a
    kernel-tileable local length; falls back otherwise).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis_name] if (mesh is not None and axis_name) else 1
    if n <= 1:
        if use_flash:
            from .flash_attention import flash_attention

            # flash_attention routes kernel-untileable T to the dense op
            return flash_attention(q, k, v, scale=scale, causal=causal)
        return attention(q, k, v, causal=causal, scale=scale)
    t = q.shape[1]
    if t % n:
        raise ValueError(
            f"sequence length {t} not divisible by ring size {n} "
            f"(mesh axis {axis_name!r})")
    shard_body = (_ring_shard_flash
                  if use_flash and flash_supported(t // n) else _ring_shard)
    body = functools.partial(
        shard_body, axis_name=axis_name, axis_size=n, causal=causal,
        scale=scale)
    axes = batch_axes(mesh, axis_name, q.shape[0])
    spec = P(axes if axes else None, axis_name, None, None)
    f = shard_map_unchecked(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)
