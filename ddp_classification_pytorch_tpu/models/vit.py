"""Flax Vision Transformer backbones with sequence-parallel (ring) attention.

The reference's model zoo is all-convolutional (torchvision/timm backbones at
BASELINE/main.py:134-144, hand-written ResNets/VGG at NESTED/model/*.py — no
attention, no sequence axis, SURVEY §2.2). This family is the framework's
long-context extension: a standard ViT classifier whose token axis can shard
over the mesh `model` axis, with exact ring attention (ops/attention.py)
rotating KV shards over ICI. It slots into the same backbone contract as the
ResNet/VGG zoos — `num_classes=0` → pooled feature vector (the NetFeat role,
NESTED/model/model.py:12-61), else logits — so every workload head (fc /
arcface / nested) composes with it unchanged.

TPU-first choices:
- patch embedding is a stride-`patch` conv → one big MXU matmul;
- bf16 compute, f32 params / LayerNorm / softmax accumulators;
- mean-pool over tokens (no CLS token): pooling commutes with the sharded
  token axis, so the head never needs a gather from shard 0;
- static shapes end to end; the ring loop is a `lax.fori_loop`;
- every op has a name in a device profile: flax writes the module path into
  `op_name` (`block3/attn/qkv`, `.../mlp_in`), and what stands outside every
  module gets a `jax.named_scope`: `patch_embed` (cast, reshape, position
  add), `ln` (a LayerNorm with the cast behind it), `mlp` (the two matmuls and
  the GELU between them), `residual`, `head` (token pool and `fc`)
  (docs/observability.md, Device-side names).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import spans
from ..ops.attention import batch_axes, flash_supported, ring_attention
from ..ops.rows_attention import rows_attention, rows_supported

# name → (patch, dim, depth, heads). feat dim == dim (backbone contract).
VIT_CONFIGS = {
    "vit_t16": (16, 192, 12, 3),
    "vit_s16": (16, 384, 12, 6),
    "vit_b16": (16, 768, 12, 12),
}
FEAT_DIMS = {name: dim for name, (_, dim, _, _) in VIT_CONFIGS.items()}


def attention_path(b: int, t: int, heads: int, d: int, dtype: Any,
                   mesh: Optional[Any] = None, seq_axis: Optional[str] = None,
                   use_flash: bool = False, flash_min_tokens: int = 0):
    """("ring" | "flash" | "rows" | "dense", batch axes of the mesh): which
    attention core `MHA` runs on `b` rows of `t` tokens, from what it can
    observe. Tokens sharded over `seq_axis` ring; `use_flash` from
    `flash_min_tokens` on streams (ops/flash_attention.py); a row that fits
    VMEM whole with heads that tile the lanes takes the whole-row kernel pair
    (ops/rows_attention.py::rows_supported), under a mesh of several devices
    inside a shard_map over the batch when `b` divides its axes; everything
    else (a toy's handful of heads, the 2-row init batch on a mesh) is the
    dense op."""
    if seq_axis is not None:
        return "ring", ()
    if use_flash and t >= flash_min_tokens and flash_supported(t):
        return "flash", ()
    axes = batch_axes(mesh, None, b) if mesh is not None else ()
    if rows_supported(t, heads, d, jnp.dtype(dtype).itemsize) and (
            mesh is None or mesh.size == 1 or axes):
        return "rows", axes
    return "dense", ()


class MHA(nn.Module):
    """Multi-head self-attention over (B, T, C) tokens. The core is chosen by
    `attention_path` as the module is traced and counted there
    (`vit_attention_total{path}`): ring-parallel when a mesh axis is
    configured (mesh/seq_axis are static module attrs), the Pallas streaming
    kernels with `use_flash` from `flash_min_tokens` tokens on, the whole-row
    kernel pair on the projection's own layout where the row fits VMEM, else
    the dense op."""

    dim: int
    heads: int
    dtype: Any = jnp.bfloat16
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None
    use_flash: bool = False
    # unsharded-path auto-pick: below this (static) token count the
    # streaming kernels are not used even when use_flash is set (0 = always).
    # The ring path is exempt — see ModelConfig.flash_min_tokens.
    flash_min_tokens: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, t, _ = x.shape
        d = self.dim // self.heads
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype, name="qkv")(x)
        path, axes = attention_path(b, t, self.heads, d, qkv.dtype, self.mesh,
                                    self.seq_axis, self.use_flash,
                                    self.flash_min_tokens)
        spans.count("vit_attention_total", path=path)
        if path == "rows":
            # q, k, v stay columns of the projection's output, o comes back
            # as the output projection reads it: no head-major copy
            out = rows_attention(qkv, self.heads,
                                 mesh=self.mesh if axes else None,
                                 batch_axes=axes)
        else:
            qkv = qkv.reshape(b, t, 3, self.heads, d)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            # ring_attention owns the rest of the dispatch: sharded token
            # axis → ring (with the flash kernel consuming each visiting KV
            # shard when use_flash), unsharded → direct flash or dense.
            out = ring_attention(
                q, k, v, mesh=self.mesh, axis_name=self.seq_axis,
                use_flash=self.use_flash and (
                    self.seq_axis is not None or t >= self.flash_min_tokens))
            out = out.reshape(b, t, self.dim)
        return nn.Dense(self.dim, dtype=self.dtype, name="proj")(out)


class Block(nn.Module):
    """Pre-LN transformer block: LN→MHA→res, LN→FFN→res. The FFN is either
    the standard MLP(4×, GELU) or, with `moe_experts` > 0, a dropless
    split-FFN mixture-of-experts (ops/moe.py) whose experts shard over the
    mesh `moe_axis` — expert parallelism.

    `ln_bf16` runs the LayerNorms in the block compute dtype instead of
    f32 — a bandwidth experiment for the HBM-bound ViT step (VERDICT r3
    #5; no chip reading exists, ROADMAP S4). Params stay
    f32 either way; default remains the f32-LN recipe."""

    dim: int
    heads: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None
    use_flash: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_axis: Optional[str] = None
    flash_min_tokens: int = 0
    ln_bf16: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        ln_dtype = self.dtype if self.ln_bf16 else jnp.float32
        with jax.named_scope("ln"):
            y = nn.LayerNorm(dtype=ln_dtype, name="ln1")(x).astype(self.dtype)
        y = MHA(self.dim, self.heads, self.dtype, self.mesh,
                self.seq_axis, self.use_flash,
                self.flash_min_tokens, name="attn")(y)
        with jax.named_scope("residual"):
            x = x + y
        with jax.named_scope("ln"):
            y = nn.LayerNorm(dtype=ln_dtype, name="ln2")(x).astype(self.dtype)
        if self.moe_experts > 0:
            from ..ops.moe import (
                load_balance_loss,
                moe_mlp,
                router_logits,
                topk_gates,
            )
            from ..parallel.mesh import DATA_AXIS

            e = self.moe_experts
            if self.dropout:
                raise ValueError(
                    "moe_experts does not support dropout (the expert mix "
                    "has no dropout slot); set --dropout 0")
            if (4 * self.dim) % e:
                raise ValueError(
                    f"moe_experts={e} must divide the FFN hidden width "
                    f"{4 * self.dim} (split-FFN param/FLOP parity)")
            hidden = (4 * self.dim) // e  # split-FFN: total params/FLOPs
            # match the dense MLP; routing redistributes capacity
            init = nn.initializers.xavier_uniform()
            router = self.param("moe_router", init, (self.dim, e), jnp.float32)
            w_in = self.param("moe_w_in", init, (e, self.dim, hidden), jnp.float32)
            b_in = self.param("moe_b_in", nn.initializers.zeros, (e, hidden), jnp.float32)
            w_out = self.param("moe_w_out", init, (e, hidden, self.dim), jnp.float32)
            b_out = self.param("moe_b_out", nn.initializers.zeros, (e, self.dim), jnp.float32)
            # batch sharding only when it divides (model.init's 2-sample
            # dummy batch doesn't; correctness never depends on it)
            # (the mesh is handed down on every multi-device run; the
            # expert layer reads it only when its experts shard over it)
            dp = (self.mesh.shape.get(DATA_AXIS, 1)
                  if self.moe_axis is not None else 1)
            batch_axis = (DATA_AXIS
                          if dp > 1 and y.shape[0] % dp == 0 else None)
            # one router evaluation feeds both the gates and the balance
            # penalty (harvested by the train step via the 'losses'
            # collection; sow accumulates across blocks)
            logits = router_logits(y, router)
            gates = topk_gates(logits, self.moe_top_k)
            self.sow("losses", "moe_aux",
                     load_balance_loss(logits, self.moe_top_k))
            y = moe_mlp(y, gates, w_in, b_in, w_out, b_out,
                        dtype=self.dtype,
                        mesh=self.mesh if self.moe_axis else None,
                        axis=self.moe_axis, batch_axis=batch_axis)
        else:
            with jax.named_scope("mlp"):
                y = nn.Dense(4 * self.dim, dtype=self.dtype, name="mlp_in")(y)
                y = nn.gelu(y)
                if self.dropout:
                    y = nn.Dropout(self.dropout, deterministic=not train)(y)
                y = nn.Dense(self.dim, dtype=self.dtype, name="mlp_out")(y)
        with jax.named_scope("residual"):
            return x + y


class ViT(nn.Module):
    """ViT backbone → pooled feature (num_classes=0) or logits.

    `seq_axis` + `mesh` switch every attention layer to ring attention with
    tokens sharded over that mesh axis. Token count (image_size/patch)² must
    then be divisible by the axis size.
    """

    patch: int = 16
    dim: int = 384
    depth: int = 12
    heads: int = 6
    num_classes: int = 0
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None
    remat: bool = False
    use_flash: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_axis: Optional[str] = None
    flash_min_tokens: int = 0
    ln_bf16: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        with jax.named_scope("patch_embed"):
            x = x.astype(self.dtype)
            x = nn.Conv(self.dim, (self.patch, self.patch),
                        strides=(self.patch, self.patch), padding="VALID",
                        dtype=self.dtype, name="patch_embed")(x)
            b, h, w, c = x.shape
            x = x.reshape(b, h * w, c)
            pos = self.param("pos_embed",
                             nn.initializers.normal(stddev=0.02),
                             (1, h * w, self.dim), jnp.float32)
            x = x + pos.astype(self.dtype)
        if self.remat:
            # checkpoint the blocks but keep every matmul (dot) output
            # saved: the ViT's recompute cost is dominated by its matmuls,
            # so the checkpoint_dots policy trades ~all of the activation
            # memory the elementwise/LN chains hold for near-zero extra
            # FLOPs — the remat policy VERDICT r3 #5 asks to exercise.
            import jax as _jax

            block_cls = nn.remat(
                Block, static_argnums=(2,),
                policy=_jax.checkpoint_policies.checkpoint_dots)
        else:
            block_cls = Block
        for i in range(self.depth):
            x = block_cls(self.dim, self.heads, self.dtype, self.dropout,
                          self.mesh, self.seq_axis, self.use_flash,
                          self.moe_experts, self.moe_top_k, self.moe_axis,
                          self.flash_min_tokens, self.ln_bf16,
                          name=f"block{i}")(x, train)
        # ln_final stays f32 even under --ln_bf16: its output feeds only the
        # f32 pool/head, so a bf16 affine here buys no matmul throughput and
        # just rounds the logits' inputs (dtype audit D6)
        with jax.named_scope("ln"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        # token mean-pool; shard-friendly (see module doc). f32 output: the
        # pool feeds the f32 head, so rounding the mean back to the compute
        # dtype would only discard mantissa bits in between (dtype audit D6)
        with jax.named_scope("head"):
            x = x.mean(axis=1, dtype=jnp.float32)
            if self.num_classes > 0:
                x = nn.Dense(self.num_classes, dtype=jnp.float32, name="fc")(x)
        return x


def build_vit(arch: str, num_classes: int = 0, dtype: Any = jnp.bfloat16,
              dropout: float = 0.0, mesh: Optional[Any] = None,
              seq_axis: Optional[str] = None, remat: bool = False,
              use_flash: bool = False, moe_experts: int = 0,
              moe_top_k: int = 2, moe_axis: Optional[str] = None,
              flash_min_tokens: int = 0, ln_bf16: bool = False) -> ViT:
    patch, dim, depth, heads = VIT_CONFIGS[arch]
    return ViT(patch=patch, dim=dim, depth=depth, heads=heads,
               num_classes=num_classes, dtype=dtype, dropout=dropout,
               mesh=mesh, seq_axis=seq_axis, remat=remat,
               use_flash=use_flash, moe_experts=moe_experts,
               moe_top_k=moe_top_k, moe_axis=moe_axis,
               flash_min_tokens=flash_min_tokens, ln_bf16=ln_bf16)
