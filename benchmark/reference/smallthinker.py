"""Plain SmallThinker-21BA3B-Instruct decoder (PowerInfer, arXiv:2507.20984;
config.json keys in brackets): the forward pass, its mean next-token
cross-entropy and, through `jax.grad`, its gradients. Straightforward
`jax.numpy`, float32, no flax, no kernel, no sorting or grouping of tokens.
Imports nothing from the program under test.

`arch`: {"vocab_size", "hidden_size", "num_layers", "num_heads",
"num_kv_heads", "head_dim", "expert_width", "num_experts", "experts_held",
"first_expert", "top_k", "rope_layout", "window_layout", "window",
"rope_theta", "rms_eps", "seq_len"}; the two layouts are 0/1 lists repeated
to the depth.

One layer, x (T, C), every projection without bias:

    h  = RMSNorm(x; g_in)                                  [rms_norm_eps]
    r  = h W_r                  router logits over ALL experts, BEFORE attention
    q, k, v = h W_q, h W_k, h W_v        H query heads, H_kv KV heads [head_dim]
    rope_layout[l] = 1: rotary embedding on q and k (rotate-half over the whole
                        head_dim, theta [rope_theta]); 0: no position at all
    a  = softmax(q k^T / sqrt(head_dim) + mask) v;  key j visible to query i
         iff j <= i and, where sliding_window_layout[l] = 1, j > i - window
    x1 = x + a W_o
    u  = RMSNorm(x1; g_post)
    S  = the top_k largest entries of r;  w = softmax(r[S])
    y  = sum over e in S of w_e W_down^e (relu(W_gate^e u) * (W_up^e u))    ReGLU
    x2 = x1 + y

then a final RMSNorm and an untied head; loss = mean over all positions of
the cross-entropy of the next token.

The chip's share (model-configs guide, section 4): only experts
`first_expert .. first_expert + experts_held - 1` exist here. The router
keeps its full width and its top_k; a chosen expert that is not held adds
nothing, and that partial result is what goes on. `vocab_size` is the
slice of the vocabulary held here.

Departures / assumptions, the program's too (its models/decoder_lm.py):
- the router reads h, the normed layer input (the published description
  says "router placed before attention" and no more);
- no QK norm, no attention bias, no shared expert, no auxiliary loss (the
  config has no such keys);
- packed rows attend across document boundaries.

How it fits: 656 M float32 parameters with their gradient and Adam's two
moments are 10.5 GB of a chip's 16 before any activation. So every layer is
a `jax.checkpoint`, attention walks the queries in blocks (one block's
(H, q, T) scores at a time, rematerialized), every HELD expert is applied to
EVERY token under its 0/1-masked gate inside a rematerialized scan over the
experts (one expert's (N, width) hidden at a time), and the head takes the
rows in blocks. None of that changes a value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels and expert banks, N(0, 0.02) embedding, norm
scales 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common
from .common import Spec

QUERY_BLOCK = 256   # queries per attention block
HEAD_BLOCK = 1024   # rows per block of the head and its loss


def quantizer(precision: str):
    """`common.quantizer`, but the fp8 control saturates: e4m3fn has no
    infinity, and on the TPU x / (max|x| / 448) can land a hair above 448 and
    convert to NaN (it did here, behind the rotary and the window layers; my
    chip run, PR 28). Clipping to the format's range first is what fp8
    training does; float32 and bfloat16 are common's."""
    if precision != "fp8":
        return common.quantizer(precision)

    def scaled(x, dtype, top):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return jnp.clip(x / scale, -top, top).astype(dtype).astype(jnp.float32) * scale

    @jax.custom_vjp
    def q(x):
        return scaled(x, jnp.float8_e4m3fn, 448.0)

    q.defvjp(lambda x: (q(x), None),
             lambda _, g: (scaled(g, jnp.float8_e5m2, 57344.0),))
    return q


def layout(arch, key: str):
    which = arch[key]
    return [int(which[i % len(which)]) for i in range(arch["num_layers"])]


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, hd = arch["hidden_size"], arch["head_dim"]
    held, width = arch["experts_held"], arch["expert_width"]

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for i in range(arch["num_layers"]):
        b = f"layer{i}"
        spec[f"{b}/norm_in/scale"] = ((c,), "ones", 0.0)
        normal(f"{b}/router", (c, arch["num_experts"]), c)
        normal(f"{b}/q/kernel", (c, arch["num_heads"] * hd), c)
        normal(f"{b}/k/kernel", (c, arch["num_kv_heads"] * hd), c)
        normal(f"{b}/v/kernel", (c, arch["num_kv_heads"] * hd), c)
        normal(f"{b}/o/kernel", (arch["num_heads"] * hd, c), arch["num_heads"] * hd)
        spec[f"{b}/norm_post/scale"] = ((c,), "ones", 0.0)
        normal(f"{b}/w_gate", (held, c, width), c)
        normal(f"{b}/w_up", (held, c, width), c)
        normal(f"{b}/w_down", (held, width, c), width)
    spec["norm_final/scale"] = ((c,), "ones", 0.0)
    normal("lm_head/kernel", (c, arch["vocab_size"]), c)
    return spec


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (B, T, H, D): dimension i paired with i + D/2 (rotate-half)."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def masked_attention(q, k, v, window, qn):
    """q (B, T, H, D), k/v (B, T, H_kv, D) -> (B, T, H, D): causal, and with
    `window` also j > i - window; masks from iota; query head h reads KV head
    h // (H / H_kv). Queries in blocks of `block`."""
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    qs = q.reshape(b, t // block, block, h_kv, h // h_kv, d).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def one(start, qb):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qn(qb), qn(k)) / math.sqrt(d)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (block, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block, t), 1)
        seen = cols <= rows
        if window:
            seen &= cols > rows - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", qn(p), qn(v))

    out = jax.lax.map(lambda xs: one(*xs),
                      (jnp.arange(t // block) * block, qs))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, d)


def held_experts(u, logits, w_gate, w_up, w_down, arch, qn):
    """u (N, C), router logits (N, E) -> the held experts' part of the
    mixture (N, C): each held expert on every token, times the token's gate
    for it (0 where the token did not choose it)."""
    vals, idx = jax.lax.top_k(logits, arch["top_k"])
    weight = jax.nn.softmax(vals, axis=-1)
    first = arch["first_expert"]

    @jax.checkpoint
    def one(y, xs):
        e, wg, wu, wd = xs
        gate = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        hid = qn(jax.nn.relu(qn(qn(u) @ qn(wg))) * qn(qn(u) @ qn(wu)))
        return y + gate[:, None] * qn(hid @ qn(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens (B, T) i32, targets (B, T) i32)`: the mean
    next-token cross-entropy. Below float32, `qn` rounds what a computation
    in that precision would hold in it: every matmul's operands and every
    layer's output; the norms' arithmetic, the router (its logits, top-k and
    gates), the softmaxes and the loss stay float32."""
    qn = quantizer(precision)
    hd = arch["head_dim"]
    ropes, windows = layout(arch, "rope_layout"), layout(arch, "window_layout")

    def layer(p, x, name, rope, window):
        b, t, c = x.shape
        h = rms_norm(x, p[f"{name}/norm_in/scale"], arch["rms_eps"])
        logits = h @ p[f"{name}/router"]
        hq = qn(h)
        q = qn(hq @ qn(p[f"{name}/q/kernel"])).reshape(b, t, arch["num_heads"], hd)
        k = qn(hq @ qn(p[f"{name}/k/kernel"])).reshape(b, t, arch["num_kv_heads"], hd)
        v = qn(hq @ qn(p[f"{name}/v/kernel"])).reshape(b, t, arch["num_kv_heads"], hd)
        if rope:
            q, k = qn(rotary(q, arch["rope_theta"])), qn(rotary(k, arch["rope_theta"]))
        a = qn(masked_attention(q, k, v, arch["window"] if window else 0, qn))
        x = qn(x + qn(a.reshape(b, t, -1) @ qn(p[f"{name}/o/kernel"])))
        u = qn(rms_norm(x, p[f"{name}/norm_post/scale"], arch["rms_eps"]))
        y = held_experts(u.reshape(b * t, c), logits.reshape(b * t, -1),
                         p[f"{name}/w_gate"], p[f"{name}/w_up"],
                         p[f"{name}/w_down"], arch, qn)
        return qn(x + y.reshape(b, t, c))

    def loss(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            x = qn(p["embed/embedding"][tokens])
            for i in range(arch["num_layers"]):
                x = jax.checkpoint(
                    lambda pp, xx, i=i: layer(pp, xx, f"layer{i}", ropes[i], windows[i])
                )(p, x)
            x = qn(rms_norm(x, p["norm_final/scale"], arch["rms_eps"]))
            n = targets.size
            block = min(HEAD_BLOCK, n)
            assert n % block == 0, (n, block)
            head = qn(p["lm_head/kernel"])

            @jax.checkpoint
            def rows(total, xs):
                xb, tb = xs
                logp = jax.nn.log_softmax(xb @ head, axis=-1)
                return total - jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1)), None

            total, _ = jax.lax.scan(
                rows, jnp.zeros((), jnp.float32),
                (x.reshape(n // block, block, -1), targets.reshape(n // block, block)))
            return total / n

    return loss
