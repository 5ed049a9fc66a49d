"""A decoder over token ids — next-token training as classification of every
position over the vocabulary, or (`objective` "block_diffusion", below) the
classification of a noised row's masked positions.

ONE layer module, described by data (`config.DecoderConfig`, which the CLI
fills — no table of variants, no second model file). Seven published models
are its fixed points: SmallThinker-21BA3B-Instruct (PowerInfer,
arXiv:2507.20984; the defaults), the DeepSeek-V3 layer (arXiv:2412.19437
§2.1-2.2) as JoyAI-LLM-Flash configures it, LFM2-8B-A1B (LiquidAI,
`lfm2_moe`: most layers mix tokens by a gated short convolution and not by
attention), Ling-3.0-flash (inclusionAI, `bailing_hybrid`: most layers
carry a state along the row by Kimi delta attention, arXiv:2510.26692 §3),
Ouro-2.6B (ByteDance, `ouro`, arXiv:2510.25741: the whole stack runs
`loops` times with the same weights, below) and Olmo-Hybrid-7B (allenai,
`olmo_hybrid`: three Gated DeltaNet layers, arXiv:2412.06464, to one of
attention, dense, the block's norms on the sub-layers' OUTPUTS only) and
SDAR-30B-A3B-Chat (JetLM, `sdar_moe`, arXiv:2510.06303: QK-normed grouped
heads and a softmax top-8 of 128 experts, every one of which the fixed points
before it have; what is new is that it is TRAINED by diffusion over blocks:
the two-stream pass, below).
With x (B, T, C), every projection without bias:

    h  = RMSNorm(x)                        input norm (pre_norm 0: h = x)
    a  = the token mixer, one of four kinds per layer:
         layers with conv_layout = 1: the gated short convolution —
                                           [B | C | X] = h W_in   (C, 3C)
                                           z   = B * X
                                           c_t = Σ_j w[j] * z_{t−(L−1)+j}
                                             depthwise over L = conv_kernel
                                             taps (LFM2: 3), causal
                                             (z_{<0} = 0), no bias, no
                                             activation
                                           a   = (C * c) W_out
                                           (it crosses document boundaries
                                           inside a packed row, as attention
                                           does)
         layers with kda_layout = 1: Kimi delta attention, H heads of
         d = head_dim —                    q~, k~, v~ = h W_q, h W_k, h W_v
                                           q, k, v = SiLU(conv(·)): depthwise,
                                             causal, conv_kernel taps (Ling: 4)
                                           q, k L2-normed per head (eps 1e-6)
                                           g_t = −5 ·
                                             sigmoid(exp(A_log) (h_t W_f + dt_bias))
                                             ∈ (−5, 0)^d: the log of the
                                             per-channel decay, A_log a head
                                           β_t = sigmoid(h_t w_β), one a head
                                           S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t)
                                                 S_{t−1} + β_t k_t v_tᵀ
                                             S (d, d) a head, 0 at the row's
                                             start, carried over documents
                                           o_t = S_tᵀ q_t / sqrt(d)
                                           a = [RMSNorm_d(o) · sigmoid(h w_g)]
                                             over the heads, W_o (one scale
                                             of d for all heads, one gate a
                                             head); no rotary embedding;
                                           in chunks of 64 tokens (ops/kda.py:
                                           its kernels at 128-wide heads, else
                                           plain XLA, 8 heads at a time). At
                                           128-wide heads and rows of whole
                                           256-token blocks nothing between
                                           the projections and W_o leaves
                                           their layout, (B, T, H·d): taps,
                                           SiLU, norms and decay are one
                                           fused op (ops/kda_prepare.py), the
                                           recurrence's kernels read and write
                                           it (`kda_flat`), the gated norm is
                                           one fused op (ops/
                                           kda_gated_norm.py); any other shape:
                                           `kda_prepare_xla` and `RMSNorm` over
                                           (B, T, H, d), in plain XLA
         layers with gdn_layout = 1: Gated DeltaNet, H heads of d_k =
         gdn_key_dim, d_v = gdn_value_dim — q, k, v as above (taps, SiLU, L2
                                           norms, q times d_k^−½)
                                           β_t = 2 sigmoid(h_t W_b) ∈ (0, 2)
                                           g_t = −exp(A_log) ·
                                             softplus(h_t W_a + dt_bias) ≤ 0,
                                             unbounded: ONE decay a head,
                                             A_log and dt_bias a head
                                           S_t = e^{g_t} S_{t−1} + β_t k_t
                                             (v_t − e^{g_t} S_{t−1}ᵀ k_t)ᵀ
                                             S (d_k, d_v) a head
                                           o_t = S_tᵀ q_t
                                           a = [RMSNorm_{d_v}(o) · SiLU(h W_g)]
                                             W_o: one scale of d_v for all
                                             heads, a gate as wide as o; in
                                             chunks of 64 tokens: kernels or
                                             plain XLA by the shapes (ops/
                                             gdn.py, `gdn_core=` at set-up)
         the others: attention(h) W_o      "gqa": H query heads on H_kv KV
                                           heads of head_dim; with qk_norm 1
                                           an RMSNorm (one scale of head_dim
                                           for all heads) on every q and k
                                           head first, with qk_norm 2 ONE
                                           RMSNorm over the whole q and the
                                           whole k projection; layers with
                                           rope_layout = 1 rotate q and k over
                                           the whole head, the others carry no
                                           position at all
                                           "mla": latent attention —
                                           c_q = RMSNorm(h W_qa);  q = c_q W_qb
                                             → H x [q_nope head_dim | q_rope rope_dim]
                                             (q_rank = 0: q = h W_q, no
                                             bottleneck and no norm)
                                           [c | k_r] = h W_kva     k_r: ONE head
                                           [k_nope | v] = RMSNorm(c) W_kvb
                                           rotary on q_rope and k_r only;
                                           scores (q_nope·k_nope + q_rope·k_r)
                                           / sqrt(head_dim + rope_dim)
                                           mask: causal, and where
                                           window_layout = 1 also j > i − window
                                           with out_gate each head's output
                                           times sigmoid(h w_g), one gate a
                                           head, before W_o
    x1 = x + a                             with sandwich_norm: x + RMSNorm(a)
    u  = RMSNorm(x1)                       (pre_norm 0: u = x1)
    y  = layers < dense_layers: W_down(act(W_gate u) · W_up u), one gated MLP
         the others: Σ_{e ∈ chosen} g_e · W_down^e(act(W_gate^e u) · W_up^e u)
                     + (shared_experts > 0) the same unit on every token
    x2 = x1 + y                            with sandwich_norm: x1 + RMSNorm(y)

The router's logits r = t W_r are taken from t = h (router_tap "pre": before
attention, as SmallThinker places it) or t = u ("post"); "softmax" scoring
chooses the top-k of r and weighs them by their softmax, "sigmoid" chooses
the top-k of sigmoid(r) + bias — with n_group > 1 inside the topk_group best
of n_group groups of experts only, a group scored by the sum of its two
largest — and weighs by the chosen sigmoid(r),
renormalised (over their sum + router_eps) and times router_scale
(ops/moe.py::route_top_k). The bias is a
leaf that selection alone reads: its gradient is zero and no rule moves it
here (ROADMAP, Reach). act = relu (ReGLU) or silu (SwiGLU). Rotary pairing:
"half" (i with i + D/2) or "interleaved" (2i with 2i + 1).

Then a final RMSNorm and a head: its own kernel, or with tied_embeddings the
embedding transposed (no `lm_head` leaf then; the table's gradient is the sum
of the lookup's scatter-add and the head's matmul). With mtp_layers = 1 a multi-token
prediction module (DeepSeek-V3 eq. 21-25) follows the last layer: with hL its
output before the final norm and `targets` the row shifted by one,

    h'  = [RMSNorm(hL) ; RMSNorm(Emb(targets))] W_eh        Emb shared
    h'' = one more routed layer, its own weights
    RMSNorm(h'') → the shared head, against the token after next

(the step, train/steps.py::_lm_loss, adds mtp_weight x that loss).

With loops = R > 1 (Ouro's `total_ut_steps`) the stack is walked R times,
the SAME leaves at every pass, positions 0..T−1 in every pass:

    h(0) = Emb(tokens)
    h(t) = RMSNorm_f(layers(h(t−1)))       t = 1..R: the final norm closes
                                           every pass, and its output is what
                                           the head and the gate read AND
                                           what pass t + 1 starts from
    λ(t) = sigmoid(h(t) · w_g + b_g)       the exit gate, one unit a token
    S(0) = 1,  S(t) = S(t−1) (1 − λ(t))
    p(t) = λ(t) S(t−1) for t < R,  p(R) = S(R−1)        Σ_t p(t) = 1

`hidden` hands on all R normed states; the step (`_lm_loss`) minimises
mean[Σ_t p(t) CE(h(t) W_head, target) − exit_beta · H(p)] with the gradient
through p too; logits, evaluation and the top-k counts are pass R's. The
passes are ONE `lax.scan` whose body is the stack (`LOOP_TRACED`): the program
holds the layers once, --remat's saved names stack over the passes.

With objective = "block_diffusion" (BD3-LM, arXiv:2503.09573; SDAR) a row x_0
of L tokens is cut into blocks of B = diffusion_block; the loader (data/
diffusion.py) draws a level t_b ∈ (0, 1] for each block and replaces each of
its tokens by the mask id with probability t_b: x_t. The model has to give,
for every masked position i of block b, p(x_0[i] | x_t[block b], x_0[blocks <
b]): the noised block sees ITSELF both ways and the CLEAN text before it. All
blocks of a row are trained in one pass over TWO STREAMS:

    tokens = [x_0 ; x_t]                   (B, 2L): one embedding lookup
    positions 0..L−1 in BOTH streams       (`rotate_half(.., streams=2)`)
    every norm, projection, router and expert on all 2L positions, the SAME
    leaves; attention under the mask, blk(i) = (i mod L) // B, query → key:
        clean  i → clean  j   iff blk(i) ≥ blk(j)
        noised i → clean  j   iff blk(i) > blk(j)
        noised i → noised j   iff blk(i) = blk(j)
        clean  i → noised j   never
    (ops/attention.py::diffusion_mask; in the flash kernels `diffusion`: L² +
    L·B of the (2L)² pairs are live and only their tiles are walked)
    hidden → the NOISED stream's L normed states (the clean stream gives keys
    and values and no loss)

and the step (train/steps.py::_diffusion_sums) minimises Σ over the masked
positions of CE(h_i W_head, x_0[i]) / t_b over all B·L positions, at the
position itself. Without the loader's noise (`__call__`, a served row) the
noised stream is the row itself: position i then reads its own block both
ways and the blocks before it, which is what one more denoising step of the
last block reads. Attention layers only, one pass, no window, no prediction
module (models/factory.py refuses the rest).

This chip may hold a share of each layer (`experts_held`, `first_expert`,
a slice of the vocabulary): the router keeps its full width, the expert
layer (ops/moe.py::sparse_moe) computes its own experts' part, and under a
`model` mesh axis > 1 the banks shard over it and a psum completes the sum.
A shared expert is held by every chip and counted once. Of a "gqa" or a
Gated DeltaNet layer it may hold a share of the heads too (`heads_held`):
that many heads' columns of every projection, tap and per-head leaf and their
rows of W_o (WHICH heads of the published layer they are is the deployment's
to say and a checkpoint loader's to read; the program is the same); what the absent heads would add to the mixer's
output is left out and the partial sum goes through the output norm to the
next layer; a whole-width QK-norm's mean square runs over the held columns.
Under a `model` mesh axis > 1 a SHARE is split evenly over it (`_over_heads`: a shard_map whose psum completes W_o's sum and the QK-norm's
sum of squares, so that the shards together ARE the layer of the held heads);
a layer that holds every head (`heads_held` 0) is replicated over the axis.

TPU-first: bf16 matmuls with f32 accumulation, f32 params, norms, router,
softmax, decays and recurrent state; attention through the Pallas flash
kernels (window band, grouped KV heads, the latent scores' shared rotary
key: ops/flash_attention.py) wherever they tile T, else the dense op;
`hidden` stops before the head so the train step can take head and loss in
row blocks (ops/lm_head.py).

Device scopes (`jax.named_scope`, docs/observability.md): `attn`, `conv`
(with `conv.in`, `conv.mix`, `conv.out` inside it), `kda` (with `kda.in`:
the five projections and the input side, the kernels `kda_prepare_fwd` /
`kda_prepare_bwd` or plain XLA; `kda.core`: the recurrence, `kda_fwd` /
`kda_states` / `kda_bwd` or plain XLA; `kda.out`: the gate, the per-head norm,
`kda_gated_norm_fwd` / `kda_gated_norm_bwd` or plain XLA, and W_o), `gdn`
(with `gdn.in`: the five projections, taps, SiLU, L2 norms, g and β;
`gdn.core`: the recurrence, `gdn_fwd` / `gdn_states` / `gdn_bwd` or plain
XLA; `gdn.out`: the gate, the per-head norm and W_o), `ffn`,
`moe.route` / `.dispatch` / `.experts` / `.combine` / `.shared`, `mtp`
(outermost, around the whole module), `lm_head`, `loop` (outermost, around
the R passes of a looped stack).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..config import DecoderConfig
from ..ops.attention import attention, flash_supported
from ..ops.flash_attention import (backward_path, diffusion_supported,
                                   flash_attention)
from ..ops import gdn, kda_prepare
from ..ops.kda import LOWER_BOUND, kda_chunked, kda_flat, takes_kernel
from ..ops.kda_gated_norm import kda_gated_norm
from ..ops.moe import GATE_ACTIVATIONS, sparse_moe
from ..utils.compat import shard_map_unchecked


def rotate_half(x: jnp.ndarray, theta: float, streams: int = 1) -> jnp.ndarray:
    """Rotary embedding over the whole head_dim of x (B, T, H, D), pairing
    dimension i with i + D/2, positions 0..T−1, in f32; with `streams` > 1
    the row is that many streams side by side, each at positions
    0..T/streams−1."""
    _, t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t // streams, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    if streams > 1:
        angle = jnp.tile(angle, (streams, 1))
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


def rotate_interleaved(x: jnp.ndarray, theta: float, streams: int = 1) -> jnp.ndarray:
    """Rotary embedding pairing dimension 2i with 2i + 1. The pairs are
    brought side to side first (even dimensions, then odd) and rotated as
    halves: q and k come out in the same permuted order, which their dot
    product does not see."""
    return rotate_half(
        jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1), theta, streams)


_ROTARY = {"half": rotate_half, "interleaved": rotate_interleaved}


def _rms(x: jnp.ndarray, scale: jnp.ndarray, eps: float, mean_square=None):
    """x / sqrt(mean square + eps) · scale in f32; `mean_square` where it is
    not the last axis's own (a norm over columns other shards hold too)."""
    x = x.astype(jnp.float32)
    if mean_square is None:
        mean_square = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * scale       # f32


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return _rms(x, scale, self.eps)


class NormScale(nn.Module):
    """`RMSNorm`'s leaf without its arithmetic: `scale` (d) under the norm's
    name, for a fused op that takes the scale as an operand."""

    @nn.compact
    def __call__(self, d: int) -> jnp.ndarray:
        return self.param("scale", nn.initializers.ones, (d,), jnp.float32)


class Kernel(nn.Module):
    """`nn.Dense`'s leaf without its matmul: `kernel` (rows, cols) under the
    projection's name, for a mixer whose heads may be split over a mesh axis
    (`_over_heads` hands each shard its columns or rows)."""

    @nn.compact
    def __call__(self, rows: int, cols: int) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.lecun_normal(), (rows, cols),
                          jnp.float32)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def _project(x: jnp.ndarray, kernel: jnp.ndarray, dtype) -> jnp.ndarray:
    """x (.., rows) kernel (rows, cols) in `dtype`, as `_dense` computes it."""
    return jnp.dot(x.astype(dtype), kernel.astype(dtype))


def _over_heads(core, h: jnp.ndarray, leaves: dict, kinds: dict, mesh, axis):
    """`core(h, leaves, psum, shards)` → the mixer's output (B, T, C): of the
    heads whose leaves it is handed, through `psum`. Without a mesh axis that
    is all of them and `psum` the identity. Under one, a shard_map hands each
    of the axis's `shards` its heads — `kinds` says how a leaf is cut: "cols"
    (.., H·d) by columns, "rows" (H·d, C) by rows, "heads" (H..,) on its one
    axis, "all" whole — and `psum` sums over the axis."""
    n = mesh.shape[axis] if (mesh is not None and axis) else 1
    if n <= 1:
        return core(h, leaves, lambda x: x, 1)
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS

    cut = {"cols": P(None, axis), "rows": P(axis, None), "heads": P(axis), "all": P()}
    dp = mesh.shape.get(DATA_AXIS, 1)
    rows = P(DATA_AXIS if dp > 1 and h.shape[0] % dp == 0 else None, None, None)
    return shard_map_unchecked(
        lambda h_, leaves_: core(h_, leaves_, lambda x: jax.lax.psum(x, axis), n),
        mesh=mesh, in_specs=(rows, {k: cut[kinds[k]] for k in leaves}),
        out_specs=rows)(h, leaves)


def head_kernel(params, cfg: DecoderConfig) -> jnp.ndarray:
    """The head's (C, V) kernel in the model's parameter tree: its own leaf,
    or the embedding transposed where the model is tied."""
    if cfg.tied_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


# how the passes of a looped stack are traced: "scan" = one `lax.scan` over
# the passes whose body is the stack, the parameters broadcast (`_looped`)
LOOP_TRACED = "scan"


def exit_distribution(gate, states: jnp.ndarray) -> jnp.ndarray:
    """The exit gate's distribution over the passes: `gate` the `exit_gate`
    leaves (kernel (C, 1), bias (1,)), `states` (R, ..., C) the normed states
    of the R passes → p (R, ...) f32, Σ over R = 1: p(t) = λ(t) Π_{s<t} (1 −
    λ(s)) with λ = sigmoid(h · w + b), and the last pass takes what is left."""
    lam = jax.nn.sigmoid(
        jnp.einsum("r...c,c->r...", states.astype(jnp.float32),
                   gate["kernel"][:, 0], precision=jax.lax.Precision.HIGHEST)
        + gate["bias"][0])
    stay = jnp.cumprod(1.0 - lam, axis=0)           # S(1..R)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])   # S(0..R−1)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def _logits(h: jnp.ndarray, kernel: jnp.ndarray, dtype) -> jnp.ndarray:
    return jnp.dot(h.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=jnp.float32)


class Head(nn.Module):
    """The untied vocabulary head: (.., C) → f32 logits (.., V)."""

    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (h.shape[-1], self.vocab_size), jnp.float32)
        return _logits(h, kernel, self.dtype)


def _causal_taps(z: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """A depthwise causal convolution of z (B, T, C) with the taps w (L, C)
    as shifted multiply-adds: tap j reads position t − (L − 1) + j, z shifted
    down the row, zeros before the row's start."""
    taps, t = w.shape[0], z.shape[1]
    return sum(w[j] * jnp.pad(z, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
               for j in range(taps))


def _takes_kernels(t: int, flash_min_tokens: int,
                   diffusion_block: Optional[int] = None) -> bool:
    """Rows of `t` tokens go through the flash kernels: where they tile T and
    beat the dense op (ModelConfig.flash_min_tokens); else the (T, T) op.
    Under `diffusion_block` the `t` positions are two streams of a row, and a
    stream is what the kernels tile and the floor is held against."""
    if diffusion_block is not None:
        return (diffusion_supported(t, diffusion_block)
                and t // 2 >= flash_min_tokens)
    return flash_supported(t) and t >= flash_min_tokens


def flash_backward_path(cfg: DecoderConfig, dtype,
                        flash_min_tokens: int) -> Optional[str]:
    """Which backward the attention layers' kernels take at the configured
    row length ("fused" | "split", ops/flash_attention.py::backward_path);
    None where no layer reaches the kernels (no attention layer, or rows the
    dense op takes)."""
    t, block = cfg.positions, cfg.diffusion_block if cfg.diffusion else None
    if (all(op != cfg.attention for op, _ in cfg.layer_kinds())
            or not _takes_kernels(t, flash_min_tokens, block)):
        return None
    widths = ((cfg.head_dim, cfg.value_dim, cfg.rope_dim)
              if cfg.attention == "mla" else (cfg.head_dim, cfg.head_dim))
    return backward_path(t, widths, jnp.dtype(dtype).itemsize)


def kda_core_path(cfg: DecoderConfig) -> Optional[str]:
    """What the delta layers' recurrence runs as at the configured sizes
    ("kernel" | "xla", ops/kda.py::takes_kernel on what `_kda` hands over);
    None where no layer is one."""
    if all(op != "kda" for op, _ in cfg.layer_kinds()):
        return None
    return "kernel" if takes_kernel(cfg.seq_len, cfg.head_dim, cfg.head_dim) else "xla"


def gdn_core_path(cfg: DecoderConfig) -> Optional[str]:
    """What the Gated DeltaNet layers' recurrence runs as at the configured
    sizes ("kernel" | "xla", ops/gdn.py::takes_kernel); None without one."""
    if all(op != "gdn" for op, _ in cfg.layer_kinds()):
        return None
    return "kernel" if gdn.takes_kernel(cfg.seq_len, cfg.gdn_key_dim, cfg.gdn_value_dim) else "xla"


def kda_prepare_path(cfg: DecoderConfig) -> Optional[str]:
    """What stands between the delta layers' projections and the recurrence
    at the configured sizes: "kernel", the fused op in the projections' own
    layout (ops/kda_prepare.py::takes_kernel: the recurrence on its kernels,
    the row whole blocks, four taps), or "xla", `kda_prepare_xla`; None where
    no layer is one."""
    if kda_core_path(cfg) is None:
        return None
    return ("kernel" if kda_prepare.takes_kernel(
        cfg.seq_len, cfg.head_dim, cfg.conv_kernel) else "xla")


def _unit(x: jnp.ndarray) -> jnp.ndarray:
    """x over its L2 norm along the last axis (a head's dims), eps 1e-6
    inside the root."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _tapped(x: jnp.ndarray, w: jnp.ndarray, heads: int) -> jnp.ndarray:
    """SiLU of the causal taps w over a projection's output x (B, T, H·d), in
    f32, split into heads → (B, T, H, d)."""
    b, t, _ = x.shape
    return jax.nn.silu(_causal_taps(x.astype(jnp.float32), w)).reshape(b, t, heads, -1)


@functools.partial(jax.checkpoint, static_argnums=(10, 11))
def kda_prepare_xla(xq, xk, xv, xf, xb, wq, wk, wv, a_log, dt_bias, heads, dtype):
    """The delta layer's input side in plain XLA: the projections' outputs
    (B, T, H·d) and (B, T, H) → q, k, v (B, T, H, d) in `dtype`, g (B, T, H, d)
    and β (B, T, H) float32. Keeps the projections; the rest is elementwise.
    What every shape the fused op does not take runs, and its reference."""
    f32 = jnp.float32
    b, t, _ = xq.shape
    hd = xq.shape[-1] // heads

    g = LOWER_BOUND * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (xf.astype(f32) + dt_bias).reshape(b, t, heads, hd))
    return ((_unit(_tapped(xq, wq, heads)) * hd ** -0.5).astype(dtype),
            _unit(_tapped(xk, wk, heads)).astype(dtype),
            _tapped(xv, wv, heads).astype(dtype), g, jax.nn.sigmoid(xb.astype(f32)))


def _a_log_init(key, shape, dtype=jnp.float32):
    """Gated DeltaNet's A ~ U(1, 16), as its log (flash-linear-attention)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus⁻¹(dt), dt log-uniform in [1e-3, 0.1] (flash-linear-attention)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@functools.partial(jax.checkpoint, static_argnums=(10,))
def gdn_prepare(xq, xk, xv, xa, xb, wq, wk, wv, a_log, dt_bias, dtype):
    """Gated DeltaNet's input side in plain XLA: the projections' outputs
    (B, T, H·d_k), (B, T, H·d_v) and (B, T, H) → q, k (B, T, H, d_k) and v
    (B, T, H, d_v) in `dtype`, g and β (B, T, H) float32. Keeps the
    projections; the rest is elementwise."""
    f32 = jnp.float32
    heads = xa.shape[-1]
    g = -jnp.exp(a_log) * jax.nn.softplus(xa.astype(f32) + dt_bias)
    q = _unit(_tapped(xq, wq, heads))
    return ((q * q.shape[-1] ** -0.5).astype(dtype),
            _unit(_tapped(xk, wk, heads)).astype(dtype),
            _tapped(xv, wv, heads).astype(dtype), g,
            2.0 * jax.nn.sigmoid(xb.astype(f32)))


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    rope: bool
    window: Optional[int]
    dtype: Any = jnp.bfloat16
    mesh: Optional[Any] = None
    expert_axis: Optional[str] = None
    flash_min_tokens: int = 1024
    routed: bool = True     # False: one dense gated MLP (a leading layer)
    mixer: str = "attn"     # "attn" | "conv" | "kda" | "gdn" (DecoderConfig.layer_kinds)

    def _gated_mlp(self, u, width: int, prefix: str):
        """W_down(act(W_gate u) · W_up u): the dense layer's feed-forward
        and the shared expert."""
        act = GATE_ACTIVATIONS[self.cfg.activation]
        gate = _dense(width, self.dtype, f"{prefix}_gate")(u)
        up = _dense(width, self.dtype, f"{prefix}_up")(u)
        return _dense(u.shape[-1], self.dtype, f"{prefix}_down")(act(gate) * up)

    def _router_logits(self, t32):
        with jax.named_scope("moe.route"):
            router = self.param("router", nn.initializers.lecun_normal(),
                                (t32.shape[-1], self.cfg.num_experts), jnp.float32)
            return jnp.einsum("btc,ce->bte", t32, router,
                              precision=jax.lax.Precision.HIGHEST)

    def _rotary(self):
        """The configured rotary embedding; under block diffusion both
        streams of the row at positions 0..L−1."""
        c = self.cfg
        return functools.partial(_ROTARY[c.rope_pairing], theta=c.rope_theta,
                                 streams=2 if c.diffusion else 1)

    def _attend(self, t: int):
        """The attention core for rows of `t` positions under the layer's
        mask: the kernels where they take them, else the dense op; causal
        (and banded by the layer's window), or the two-stream mask."""
        c = self.cfg
        block = c.diffusion_block if c.diffusion else None
        core = (flash_attention if _takes_kernels(t, self.flash_min_tokens, block)
                else attention)
        if c.diffusion:
            return functools.partial(core, diffusion_block=block)
        return functools.partial(core, causal=True, window=self.window)

    def _qkv(self, h):
        """Latent attention's → q, k, v (B, T, heads, ·) and the scores' second
        part (q_rope, k_rope)."""
        c = self.cfg
        b, t, _ = h.shape
        rotary = self._rotary()
        # latent attention: queries through a normed bottleneck (or, with
        # q_rank 0, straight from h); one normed latent gives every head its
        # position-free key and its value, and one rotary key head serves all
        # query heads
        heads, hd, dr = c.num_heads, c.head_dim, c.rope_dim
        if c.q_rank:
            cq = RMSNorm(c.rms_eps, name="q_norm")(
                _dense(c.q_rank, self.dtype, "q_a")(h)).astype(self.dtype)
            q = _dense(heads * (hd + dr), self.dtype, "q_b")(cq)
        else:
            q = _dense(heads * (hd + dr), self.dtype, "q")(h)
        q = q.reshape(b, t, heads, hd + dr)
        kv = _dense(c.kv_rank + dr, self.dtype, "kv_a")(h)
        ckv = RMSNorm(c.rms_eps, name="kv_norm")(
            kv[..., :c.kv_rank]).astype(self.dtype)
        kv_b = _dense(heads * (hd + c.value_dim), self.dtype, "kv_b")(ckv)
        kv_b = kv_b.reshape(b, t, heads, hd + c.value_dim)
        q_rope, k_rope = q[..., hd:], kv[..., c.kv_rank:].reshape(b, t, 1, dr)
        if self.rope:
            q_rope, k_rope = rotary(q_rope), rotary(k_rope)
        return q[..., :hd], kv_b[..., :hd], kv_b[..., hd:], (q_rope, k_rope)

    def _short_conv(self, h):
        """LFM2's token mixer: h (B, T, C) → (B, T, C). `W_in` is ONE matmul
        of 3C columns; the taps are shifted multiply-adds in f32 over the
        gate product as the compute dtype holds it; plain XLA."""
        dim, taps = h.shape[-1], self.cfg.conv_kernel
        with jax.named_scope("conv.in"):
            gate_b, gate_c, xs = jnp.split(
                _dense(3 * dim, self.dtype, "conv_in")(h), 3, axis=-1)
        with jax.named_scope("conv.mix"):
            w = self.param("conv_taps", nn.initializers.lecun_normal(),
                           (taps, dim), jnp.float32)
            mixed = _causal_taps((gate_b * xs).astype(jnp.float32), w)
            y = (gate_c.astype(jnp.float32) * mixed).astype(self.dtype)
        with jax.named_scope("conv.out"):
            return _dense(dim, self.dtype, "conv_out")(y)

    def _kda(self, h):
        """Kimi delta attention's block: h (B, T, C) → (B, T, C). q, k, v
        through a depthwise causal convolution of `conv_kernel` taps and
        SiLU, q and k L2-normed per head, the per-channel log decay g and β
        from h, the recurrence in chunks (ops/kda.py: its kernels where the
        head's tiles are whole, else plain XLA), a per-head RMSNorm gated by
        one sigmoid a head, W_o; no rotary embedding. Where the input side's
        fused op takes the shapes (`kda_prepare_path`), q, k, v, g and o stay
        (B, T, H·d) from the projections to W_o; else (B, T, H, d)."""
        c = self.cfg
        b, t, dim = h.shape
        heads, hd, taps = c.num_heads, c.head_dim, c.conv_kernel
        f32 = jnp.float32

        def project(name, width=heads * hd):
            return _dense(width, self.dtype, f"kda_{name}")(h)

        def taps_of(name):
            return self.param(f"kda_taps_{name}", nn.initializers.lecun_normal(),
                              (taps, heads * hd), f32)

        fused = kda_prepare.takes_kernel(t, hd, taps)
        with jax.named_scope("kda.in"):
            xs = [project(name) for name in "qkvf"] + [project("beta", heads)]
            small = (taps_of("q"), taps_of("k"), taps_of("v"),
                     self.param("kda_a_log", nn.initializers.zeros, (heads,), f32),
                     self.param("kda_dt_bias", nn.initializers.constant(-4.0),
                                (heads * hd,), f32))
            if fused:   # q, k, v, g stay (B, T, H·d), as the kernels read them
                q, k, v, g, beta = kda_prepare.kda_prepare(*xs, *small)
            else:
                q, k, v, g, beta = kda_prepare_xla(*xs, *small, heads, self.dtype)
        with jax.named_scope("kda.core"):
            # named for --remat's policy (DecoderLM.setup): the layer's
            # recomputed forward keeps o and does not walk the states again
            core = kda_flat if fused else kda_chunked
            o = checkpoint_name(core(q, k, v, g, beta, dtype=self.dtype), "kda_out")
        with jax.named_scope("kda.out"):
            gate = jax.nn.sigmoid(_dense(heads, self.dtype, "kda_gate")(h).astype(f32))
            if fused:   # o too stays (B, T, H·d), as W_o reads it
                y = kda_gated_norm(o, gate, NormScale(name="kda_norm")(hd),
                                   eps=c.rms_eps, dtype=self.dtype)
            else:
                y = (RMSNorm(c.rms_eps, name="kda_norm")(o) * gate[..., None]
                     ).astype(self.dtype).reshape(b, t, -1)
            return _dense(dim, self.dtype, "kda_o")(y)

    def _share_axis(self):
        """(mesh, axis) a share of the heads is split over: the experts' axis
        where `heads_held` is set; else (None, None): a layer that holds every
        head is replicated over any axis, as its other leaves are."""
        return (self.mesh, self.expert_axis) if self.cfg.heads_held else (None, None)

    def _gdn(self, h):
        """Gated DeltaNet's block (ops/gdn.py has the equations): h (B, T, C)
        → (B, T, C), of the heads held here (`DecoderConfig.heads`). Its
        leaves are declared here at those heads' widths and the arithmetic is
        `core`, which `_over_heads` runs on all of them or, where a share is
        split over a mesh axis, on each shard's."""
        c = self.cfg
        dim, heads, dk, dv = h.shape[-1], c.heads, c.gdn_key_dim, c.gdn_value_dim
        f32, lecun = jnp.float32, nn.initializers.lecun_normal()
        leaves, kinds = {}, {}
        for name, width in (("q", dk), ("k", dk), ("v", dv), ("gate", dv),
                            ("a", 1), ("beta", 1)):
            leaves[name] = Kernel(name=f"gdn_{name}")(dim, heads * width)
            kinds[name] = "cols"
        for name, width in (("q", dk), ("k", dk), ("v", dv)):
            leaves[f"taps_{name}"] = self.param(
                f"gdn_taps_{name}", lecun, (c.conv_kernel, heads * width), f32)
            kinds[f"taps_{name}"] = "cols"
        leaves["a_log"] = self.param("gdn_a_log", _a_log_init, (heads,), f32)
        leaves["dt_bias"] = self.param("gdn_dt_bias", _dt_bias_init, (heads,), f32)
        kinds.update(a_log="heads", dt_bias="heads")
        leaves["norm"], kinds["norm"] = NormScale(name="gdn_norm")(dv), "all"
        leaves["o"], kinds["o"] = Kernel(name="gdn_o")(heads * dv, dim), "rows"

        def core(h, w, psum, shards):
            b, t, _ = h.shape
            with jax.named_scope("gdn.in"):
                xs = [_project(h, w[n], self.dtype) for n in ("q", "k", "v", "a", "beta")]
                q, k, v, g, beta = gdn_prepare(
                    *xs, w["taps_q"], w["taps_k"], w["taps_v"], w["a_log"],
                    w["dt_bias"], f32 if gdn.takes_kernel(t, dk, dv) else self.dtype)
            with jax.named_scope("gdn.core"):
                # named for --remat's policy, as `kda_out` is
                o = checkpoint_name(
                    gdn.gdn_chunked(q, k, v, g, beta, dtype=self.dtype), "gdn_out")
            with jax.named_scope("gdn.out"):
                gate = jax.nn.silu(_project(h, w["gate"], self.dtype).astype(f32))
                y = (_rms(o, w["norm"], c.rms_eps) * gate.reshape(o.shape)
                     ).astype(self.dtype).reshape(b, t, -1)
                return psum(_project(y, w["o"], self.dtype))

        return _over_heads(core, h, leaves, kinds, *self._share_axis())

    def _grouped_attention(self, h):
        """Grouped-query attention: h (B, T, C) → (B, T, C), of the heads held
        here (`DecoderConfig.heads`: all of them, or `heads_held`). The leaves
        are W_q, W_k, W_v's columns and W_o's rows of those heads and the
        arithmetic is `core`, which `_over_heads` runs on all of them or,
        where a share is split over a mesh axis, on each shard's. A
        whole-width QK-norm's mean square runs over the held columns (all
        shards')."""
        c = self.cfg
        dim, hd = h.shape[-1], c.head_dim
        leaves = {"q": Kernel(name="q")(dim, c.heads * hd),
                  "k": Kernel(name="k")(dim, c.kv_heads * hd),
                  "v": Kernel(name="v")(dim, c.kv_heads * hd),
                  "o": Kernel(name="o")(c.heads * hd, dim)}
        kinds = dict(q="cols", k="cols", v="cols", o="rows")
        if c.qk_norm == 2:
            leaves.update(q_norm=NormScale(name="q_norm")(c.heads * hd),
                          k_norm=NormScale(name="k_norm")(c.kv_heads * hd))
            kinds.update(q_norm="heads", k_norm="heads")
        elif c.qk_norm:
            leaves.update(q_norm=NormScale(name="q_head_norm")(hd),
                          k_norm=NormScale(name="k_head_norm")(hd))
            kinds.update(q_norm="all", k_norm="all")
        if c.out_gate:
            leaves["o_gate"], kinds["o_gate"] = Kernel(name="o_gate")(dim, c.heads), "cols"
        rotary = self._rotary()

        def core(h, w, psum, shards):
            b, t, _ = h.shape
            q, k, v = (_project(h, w[n], self.dtype) for n in "qkv")
            if c.qk_norm == 2:
                def whole(x, scale):   # the mean over every shard's columns
                    total = psum(jnp.sum(jnp.square(x.astype(jnp.float32)), -1,
                                         keepdims=True))
                    return _rms(x, scale, c.rms_eps, total / (x.shape[-1] * shards)
                                ).astype(self.dtype)

                q, k = whole(q, w["q_norm"]), whole(k, w["k_norm"])
            q, k, v = (x.reshape(b, t, -1, hd) for x in (q, k, v))
            if c.qk_norm == 1:
                q = _rms(q, w["q_norm"], c.rms_eps).astype(self.dtype)
                k = _rms(k, w["k_norm"], c.rms_eps).astype(self.dtype)
            if self.rope:
                q, k = rotary(q), rotary(k)
            a = self._attend(t)(q, k, v)
            if c.out_gate:
                gate = jax.nn.sigmoid(_project(h, w["o_gate"], self.dtype)
                                      .astype(jnp.float32))
                a = (a.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
            return psum(_project(a.reshape(b, t, -1), w["o"], self.dtype))

        return _over_heads(core, h, leaves, kinds, *self._share_axis())

    def _attention(self, h):
        if self.cfg.attention == "gqa":
            return self._grouped_attention(h)
        b, t, dim = h.shape
        q, k, v, (q_rope, k_rope) = self._qkv(h)
        a = self._attend(t)(q, k, v, q_rope=q_rope, k_rope=k_rope)
        if self.cfg.out_gate:   # one sigmoid a head on the head's output
            gate = jax.nn.sigmoid(_dense(self.cfg.num_heads, self.dtype, "o_gate")(
                h).astype(jnp.float32))
            a = (a.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
        return _dense(dim, self.dtype, "o")(a.reshape(b, t, -1))

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        c = self.cfg
        b, t, dim = x.shape
        def in_norm(y, name):
            # pre_norm 0: no norm on a sub-layer's input (Olmo's block norms
            # the outputs only)
            if not c.pre_norm:
                return y.astype(jnp.float32)
            return RMSNorm(c.rms_eps, name=name)(y)

        h32 = in_norm(x, "norm_in")
        if self.routed and c.router_tap == "pre":
            logits = self._router_logits(h32)
        def out_norm(y, name):
            # the sandwich: a second norm, on the sub-layer's output
            if not c.sandwich_norm:
                return y
            return RMSNorm(c.rms_eps, name=name)(y).astype(self.dtype)

        with jax.named_scope(self.mixer):
            mix = {"attn": self._attention, "conv": self._short_conv,
                   "kda": self._kda, "gdn": self._gdn}[self.mixer]
            x = x + out_norm(mix(h32.astype(self.dtype)), "norm_mix_out")
        u32 = in_norm(x, "norm_post")
        u = u32.astype(self.dtype)
        if not self.routed:
            with jax.named_scope("ffn"):
                y = out_norm(self._gated_mlp(u, c.dense_width, "ffn"), "norm_ffn_out")
                return x + y.astype(x.dtype), None
        if c.router_tap != "pre":
            logits = self._router_logits(u32)
        route = None
        if c.router == "sigmoid":
            route = dict(scoring="sigmoid", scale=c.router_scale,
                         eps=c.router_eps,
                         bias=self.param("router_bias", nn.initializers.zeros,
                                         (c.num_experts,), jnp.float32))
            if c.n_group > 1:
                route.update(n_group=c.n_group, topk_group=c.topk_group)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", init, (c.held, dim, c.expert_width), jnp.float32)
        w_up = self.param("w_up", init, (c.held, dim, c.expert_width), jnp.float32)
        w_down = self.param("w_down", init, (c.held, c.expert_width, dim), jnp.float32)
        batch_axis = None
        if self.mesh is not None:
            from ..parallel.mesh import DATA_AXIS

            # batch sharding only when it divides (model.init's 2-row
            # dummy batch may not; correctness never depends on it)
            dp = self.mesh.shape.get(DATA_AXIS, 1)
            batch_axis = DATA_AXIS if dp > 1 and b % dp == 0 else None
        y, load = sparse_moe(
            u.reshape(b * t, dim), logits.reshape(b * t, -1), w_gate, w_up,
            w_down, top_k=c.top_k, first_expert=c.first_expert,
            dtype=self.dtype, mesh=self.mesh, axis=self.expert_axis,
            batch_axis=batch_axis, activation=c.activation, route=route)
        y = y.reshape(b, t, dim)
        if c.shared_experts:
            with jax.named_scope("moe.shared"):
                y = y + self._gated_mlp(
                    u, c.shared_experts * c.expert_width, "shared")
        return x + out_norm(y, "norm_ffn_out").astype(x.dtype), load


class MTPModule(nn.Module):
    """Multi-token prediction, depth 1 (DeepSeek-V3 eq. 21-23): the last
    layer's output (before the final norm) and the embedding of the NEXT
    token, each normed, joined in the paper's order [h ; e], projected to C
    and put through one more routed layer → final-normed states whose head
    output predicts the token after next."""

    make_layer: Any         # name → a routed DecoderLayer
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h_last: jnp.ndarray, emb_next: jnp.ndarray):
        joined = jnp.concatenate(
            [RMSNorm(self.eps, name="norm_h")(h_last),
             RMSNorm(self.eps, name="norm_e")(emb_next)], axis=-1)
        x = _dense(h_last.shape[-1], self.dtype, "proj")(joined.astype(self.dtype))
        x, load = self.make_layer("layer")(x)
        return RMSNorm(self.eps, name="norm_final")(x).astype(self.dtype), load


def _one_pass(mdl: "DecoderLM", x: jnp.ndarray, _):
    """One pass of a looped stack, the body of `_looped`'s scan: the layers,
    then the final norm → (what the next pass starts from, what the head and
    the gate read): the same normed states."""
    for layer in mdl.layers:
        x = layer(x)[0]
    out = mdl.norm_final(x).astype(mdl.dtype)
    return out, out


class DecoderLM(nn.Module):
    """tokens (B, T) i32 → logits (B, T, V) f32; `hidden` → the final-normed
    states (B, T, C) — of a looped stack (R, B, T, C), one set a pass — and
    the token-slot loads of the held experts, one row a routing layer
    (`DecoderConfig.moe_layer_names`) — what the row-blocked head and the
    step's metrics take."""

    cfg: DecoderConfig
    dtype: Any = jnp.bfloat16
    remat: bool = False
    mesh: Optional[Any] = None
    expert_axis: Optional[str] = None
    flash_min_tokens: int = 1024

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size,
                              embedding_init=nn.initializers.normal(0.02),
                              name="embed")
        # --remat recomputes a layer in its backward pass but for the flash
        # kernels' output and logsumexp (117 MB a layer at 2 x 8,192 tokens)
        # and the delta layers' recurrence's output (KDA's 134 MB a layer at
        # 8,192 tokens, Gated DeltaNet's 94 MB at 15 heads of 192): saving
        # them spares a second run of the forward kernel, and of the walk
        # over the chunks' states
        layer = (nn.remat(DecoderLayer, policy=jax.checkpoint_policies
                          .save_only_these_names("flash_out", "flash_lse", "kda_out",
                                                 "gdn_out"))
                 if self.remat else DecoderLayer)

        def build(i: int, name: str):
            mixer = ("conv" if c.conv_layout[i % len(c.conv_layout)] else
                     "kda" if c.kda_layout[i % len(c.kda_layout)] else
                     "gdn" if c.gdn_layout[i % len(c.gdn_layout)] else "attn")
            return layer(c, bool(c.rope_layout[i % len(c.rope_layout)]),
                         c.window if c.window_layout[i % len(c.window_layout)]
                         else None,
                         self.dtype, self.mesh, self.expert_axis,
                         self.flash_min_tokens, i >= c.dense_layers, mixer,
                         name=name)

        self.layers = [build(i, f"layer{i}") for i in range(c.num_layers)]
        self.norm_final = RMSNorm(c.rms_eps, name="norm_final")
        if not c.tied_embeddings:
            self.lm_head = Head(c.vocab_size, self.dtype, name="lm_head")
        if c.mtp_layers:
            # its layer continues the layouts: index = the depth
            self.mtp = MTPModule(functools.partial(build, c.num_layers),
                                 c.rms_eps, self.dtype, name="mtp")
        if c.loops > 1:
            # read by the step (`exit_distribution`), from the leaves
            self.exit_gate = nn.Dense(1, name="exit_gate")

    def _looped(self, x: jnp.ndarray) -> jnp.ndarray:
        """x (B, T, C) → the R passes' normed states (R, B, T, C): one scan
        over the passes, the parameters broadcast (the same leaves at every
        pass; their gradient sums over the passes)."""
        if self.is_initializing():   # one plain pass makes every leaf
            return jnp.stack([_one_pass(self, x, None)[1]] * self.cfg.loops)
        with jax.named_scope("loop"):
            return nn.scan(_one_pass, variable_broadcast="params",
                           split_rngs={"params": False},
                           length=self.cfg.loops)(self, x, None)[1]

    def hidden(self, tokens: jnp.ndarray, train: bool = True,
               targets: Optional[jnp.ndarray] = None):
        """→ (h, loads). With `targets` (the row shifted by one) and a
        prediction module also its states: (h, loads, h_mtp), the module's
        loads in the last row. Under block diffusion `tokens` is the clean
        row x_0 (B, L) and `targets` [x_t ; j] (B, 2, L) as the loader makes
        them (data/diffusion.py): ONE lookup and one pass over [x_0 ; x_t]
        (B, 2L), and h is the NOISED stream's L states; the loads count both
        streams' positions. Without `targets` the noised stream is the row
        itself: what a served row's block reads (its own block both ways,
        the blocks before it), at twice the positions."""
        c = self.cfg
        if c.diffusion:
            noised = tokens if targets is None else targets[:, 0]
            tokens = jnp.concatenate([tokens, noised], axis=1)
        x = self.embed(tokens).astype(self.dtype)
        if self.cfg.loops > 1:   # dense layers only: no loads
            return self._looped(x), jnp.zeros((0, self.cfg.held), jnp.int32)
        loads = []
        for layer in self.layers:
            x, load = layer(x)
            if load is not None:
                loads.append(load)
        if c.diffusion:
            x, targets = x[:, x.shape[1] // 2:], None
        out = self.norm_final(x).astype(self.dtype)
        if targets is None or not self.cfg.mtp_layers:
            # a decoder of dense layers only routes nothing: no row
            return out, (jnp.stack(loads) if loads
                         else jnp.zeros((0, self.cfg.held), jnp.int32))
        with jax.named_scope("mtp"):
            h_mtp, load = self.mtp(x, self.embed(targets).astype(self.dtype))
        return out, jnp.stack(loads + [load]), h_mtp

    def __call__(self, tokens: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        # init has to reach the prediction module's leaves too
        targets = (tokens if self.is_initializing() and self.cfg.mtp_layers
                   else None)
        h = self.hidden(tokens, train, targets)[0]
        if self.cfg.loops > 1:
            h = h[-1]   # the last pass is the one served
            if self.is_initializing():
                self.exit_gate(h)
        with jax.named_scope("lm_head"):
            if self.cfg.tied_embeddings:
                return _logits(h, self.embed.embedding.T, self.dtype)
            return self.lm_head(h)
