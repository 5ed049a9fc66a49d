"""A decoder over token ids — next-token training as classification of every
position over the vocabulary.

The layer is the one SmallThinker-21BA3B-Instruct publishes (PowerInfer,
arXiv:2507.20984; every size comes from `config.DecoderConfig`, which the CLI
fills — no table of variants here). With x (B, T, C), every projection
without bias:

    h  = RMSNorm(x)                        input norm
    r  = h W_r                             router logits, taken BEFORE attention
    a  = attention(q, k, v)                H query heads on H_kv KV heads;
                                           layers with rope_layout = 1 rotate q
                                           and k (rotate-half, whole head_dim),
                                           the others carry no position at all;
                                           mask: causal, and where
                                           window_layout = 1 also j > i − window
    x1 = x + a W_o
    u  = RMSNorm(x1)
    y  = Σ_{e ∈ top-k(r)} softmax(r[top-k])_e · W_down^e(relu(W_gate^e u) · W_up^e u)
    x2 = x1 + y

then a final RMSNorm and an untied head. No dense feed-forward layer, no
shared expert, no auxiliary loss. The router reads `h` (the normed input):
the published description says "router placed before attention" and no more.

This chip may hold a share of each layer (`experts_held`, `first_expert`,
a slice of the vocabulary): the router keeps its full width, the expert
layer (ops/moe.py::sparse_moe) computes its own experts' part, and under a
`model` mesh axis > 1 the banks shard over it and a psum completes the sum.

TPU-first: bf16 matmuls with f32 accumulation, f32 params, norms, router and
softmax; attention through the Pallas flash kernels (window band and grouped
KV heads, ops/flash_attention.py) wherever they tile T, else the dense op;
`hidden` stops before the head so the train step can take head and loss in
row blocks (ops/lm_head.py).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config import DecoderConfig
from ..ops.attention import attention, flash_supported
from ..ops.flash_attention import flash_attention
from ..ops.moe import sparse_moe


def rotate_half(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding over the whole head_dim of x (B, T, H, D), pairing
    dimension i with i + D/2, positions 0..T−1, in f32."""
    _, t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) * scale       # f32


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class Head(nn.Module):
    """The untied vocabulary head: (.., C) → f32 logits (.., V)."""

    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (h.shape[-1], self.vocab_size), jnp.float32)
        return jnp.dot(h.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    rope: bool
    window: Optional[int]
    dtype: Any = jnp.bfloat16
    mesh: Optional[Any] = None
    expert_axis: Optional[str] = None
    flash_min_tokens: int = 1024

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        c = self.cfg
        b, t, dim = x.shape
        h32 = RMSNorm(c.rms_eps, name="norm_in")(x)
        with jax.named_scope("moe.route"):
            router = self.param("router", nn.initializers.lecun_normal(),
                                (dim, c.num_experts), jnp.float32)
            logits = jnp.einsum("btc,ce->bte", h32, router,
                                precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope("attn"):
            h = h32.astype(self.dtype)
            q = _dense(c.num_heads * c.head_dim, self.dtype, "q")(h)
            k = _dense(c.num_kv_heads * c.head_dim, self.dtype, "k")(h)
            v = _dense(c.num_kv_heads * c.head_dim, self.dtype, "v")(h)
            q = q.reshape(b, t, c.num_heads, c.head_dim)
            k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
            v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
            if self.rope:
                q, k = rotate_half(q, c.rope_theta), rotate_half(k, c.rope_theta)
            # the kernels where they tile T and beat the dense op
            # (ModelConfig.flash_min_tokens), else the (T, T) op
            core = (flash_attention
                    if flash_supported(t) and t >= self.flash_min_tokens
                    else attention)
            a = core(q, k, v, causal=True, window=self.window)
            x = x + _dense(dim, self.dtype, "o")(a.reshape(b, t, -1))
        u = RMSNorm(c.rms_eps, name="norm_post")(x).astype(self.dtype)
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
            batch_axis=batch_axis)
        return x + y.reshape(b, t, dim).astype(x.dtype), load


class DecoderLM(nn.Module):
    """tokens (B, T) i32 → logits (B, T, V) f32; `hidden` → the final-normed
    states (B, T, C) and the per-layer token-slot loads of the held experts
    (L, e) — what the row-blocked head and the step's metrics take."""

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
        # kernels' output and logsumexp (117 MB a layer at 2 x 8,192 tokens):
        # saving them spares a second run of the forward kernel
        layer = (nn.remat(DecoderLayer, policy=jax.checkpoint_policies
                          .save_only_these_names("flash_out", "flash_lse"))
                 if self.remat else DecoderLayer)
        rope, window = c.layout(c.rope_layout), c.layout(c.window_layout)
        self.layers = [
            layer(c, bool(rope[i]), c.window if window[i] else None,
                  self.dtype, self.mesh, self.expert_axis,
                  self.flash_min_tokens, name=f"layer{i}")
            for i in range(c.num_layers)]
        self.norm_final = RMSNorm(c.rms_eps, name="norm_final")
        self.lm_head = Head(c.vocab_size, self.dtype, name="lm_head")

    def hidden(self, tokens: jnp.ndarray, train: bool = True):
        x = self.embed(tokens).astype(self.dtype)
        loads = []
        for layer in self.layers:
            x, load = layer(x)
            loads.append(load)
        return self.norm_final(x).astype(self.dtype), jnp.stack(loads)

    def __call__(self, tokens: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        h, _ = self.hidden(tokens, train)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)
