"""Plain LFM2-8B-A1B decoder (LiquidAI, `config.json`, model_type `lfm2_moe`;
config keys in brackets): the forward pass, its mean next-token cross-entropy
and, through `jax.grad`, its gradients. Straightforward `jax.numpy`, float32,
no flax, no kernel, no sorting or grouping of tokens. Imports nothing from
the program under test. The operator and the attention are those of
`transformers/models/lfm2/modeling_lfm2.py` (`Lfm2ShortConv.slow_forward`,
`Lfm2Attention`), the router `Lfm2MoeSparseMoeBlock.route_tokens_to_experts`.

`arch`: {"vocab_size", "hidden_size", "num_layers" [num_hidden_layers],
"num_heads", "num_kv_heads", "head_dim" [hidden_size / num_attention_heads],
"conv_layout" [layer_types: 1 = "conv", 0 = "full_attention"; a list
repeated to the depth], "conv_kernel" [conv_L_cache], "dense_layers"
[num_dense_layers], "dense_width" [intermediate_size], "expert_width"
[moe_intermediate_size], "num_experts" [the router's width], "experts_held",
"first_expert", "top_k" [num_experts_per_tok], "router_scale"
[routed_scaling_factor], "router_eps", "rope_theta", "rms_eps" [norm_eps],
"seq_len"}.

One layer, x (T, C), every projection without bias [conv_bias false]:

    h = RMSNorm(x)                                          [operator_norm]
    conv_layout[i] = 1:  [B | C | X] = h W_in     W_in (C, 3C), three chunks
                                                  of C columns in this order
                         z   = B * X
                         c_t = sum_{j=0..L-1} w[j] * z_{t-(L-1)+j}   z_{<0} = 0
                               depthwise, causal, no bias, NO activation
                         a   = (C * c) W_out
    conv_layout[i] = 0:  q = RMSNorm(h W_q per head), k = RMSNorm(h W_k per head)
                               one scale of head_dim each, shared by the heads
                         q, k <- rotary(theta; dimension i paired with
                               i + head_dim / 2, positions 0..T-1)
                         a = softmax_causal(q k^T / sqrt(head_dim)) v  W_o
                               query head h reads KV head h // (H / H_kv)
    x1 = x + a
    u  = RMSNorm(x1)                                        [ffn_norm]
    layers < dense_layers:  x2 = x1 + W_down(silu(W_gate u) * W_up u)
    the others:  s = sigmoid(u W_r), float32
                 chosen = the top_k of s + b               [use_expert_bias]
                 g_e = s_e / (sum over the chosen of s + router_eps)
                       * router_scale                      [norm_topk_prob]
                 x2 = x1 + sum over the chosen of g_e E_e(u)
                 E: W_down(silu(W_gate u) * W_up u);  no shared expert

then the final RMSNorm [embedding_norm] and logits = h Emb^T: the head IS the
embedding [tie_word_embeddings, `Lfm2Config`'s default], so the table's
gradient is the sum of the lookup's and the head's. `b` steers the choice
and nothing else: its gradient is exactly zero.

The chip's share (model-configs guide, section 4): only experts
`first_expert .. first_expert + experts_held - 1` exist here. The router
keeps its full width and its top_k; a chosen expert that is not held adds
nothing, and that partial result is what goes on. `vocab_size` is the slice
of the vocabulary held here (embedding, head and loss are over the slice).

Departures / assumptions, the program's too:
- the taps are stored (L, C), the published `Conv1d` weight (C, 1, L) with
  its axes swapped; the convolution is written as the sum over L shifted
  copies of z, which is what `Conv1d(padding=L-1)[..., :T]` computes;
- `b` stays as seeded: the rule that moves it from the step's load counts is
  no gradient and not part of the loss; no balance loss;
- packed rows mix (the convolution's taps) and attend across document
  boundaries.

How it fits: 508 M float32 parameters with their gradient and Adam's two
moments are 8.1 GB of a chip's 16.9. Every layer walks the rows of the batch
ONE AT A TIME, each row a `jax.checkpoint` (rows do not see each other: the
operator and attention are within a row, the experts per token), attention
walks the queries in blocks, every HELD expert is applied to EVERY token
under its masked gate inside a scan whose expert is rematerialized, and the
head takes the rows in blocks. None of that changes a value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels, taps (fan-in L) and expert banks, N(0, 0.02)
embedding, norm scales 1, and b ~ N(0, 0.1): non-zero, so that choosing by
s + b and weighting by s can be told apart.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .joyai_llm_flash import BIAS_STD, HEAD_BLOCK, by_rows, gated_mlp, held_experts
from .smallthinker import masked_attention, quantizer, rms_norm, rotary


def layer_kinds(arch):
    """(name, conv?, routed?) of every layer."""
    which = arch["conv_layout"]
    return [(f"layer{i}", bool(which[i % len(which)]), i >= arch["dense_layers"])
            for i in range(arch["num_layers"])]


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, hd = arch["hidden_size"], arch["head_dim"]
    held, width, taps = arch["experts_held"], arch["expert_width"], arch["conv_kernel"]

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    def ones(name, n):
        spec[name] = ((n,), "ones", 0.0)

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for b, conv, routed in layer_kinds(arch):
        ones(f"{b}/norm_in/scale", c)
        if conv:
            normal(f"{b}/conv_in/kernel", (c, 3 * c), c)
            normal(f"{b}/conv_taps", (taps, c), taps)
            normal(f"{b}/conv_out/kernel", (c, c), c)
        else:
            normal(f"{b}/q/kernel", (c, arch["num_heads"] * hd), c)
            normal(f"{b}/k/kernel", (c, arch["num_kv_heads"] * hd), c)
            normal(f"{b}/v/kernel", (c, arch["num_kv_heads"] * hd), c)
            ones(f"{b}/q_head_norm/scale", hd)
            ones(f"{b}/k_head_norm/scale", hd)
            normal(f"{b}/o/kernel", (arch["num_heads"] * hd, c), arch["num_heads"] * hd)
        ones(f"{b}/norm_post/scale", c)
        if not routed:
            normal(f"{b}/ffn_gate/kernel", (c, arch["dense_width"]), c)
            normal(f"{b}/ffn_up/kernel", (c, arch["dense_width"]), c)
            normal(f"{b}/ffn_down/kernel", (arch["dense_width"], c), arch["dense_width"])
            continue
        normal(f"{b}/router", (c, arch["num_experts"]), c)
        spec[f"{b}/router_bias"] = ((arch["num_experts"],), "normal", BIAS_STD)
        normal(f"{b}/w_gate", (held, c, width), c)
        normal(f"{b}/w_up", (held, c, width), c)
        normal(f"{b}/w_down", (held, width, c), width)
    ones("norm_final/scale", c)
    return spec


def short_conv(z, w):
    """z (B, T, C), taps w (L, C) -> c_t = sum_j w[j] * z_{t-(L-1)+j}: the
    explicit sum over L copies of z shifted down the row, zeros before its
    start."""
    t, taps = z.shape[1], w.shape[0]
    return sum(w[j] * jnp.pad(z, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
               for j in range(taps))


def route(logits, bias, arch):
    """(N, E) float32 logits -> (chosen ids (N, k), their weights (N, k))."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, arch["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True) + arch["router_eps"]
    return idx, chosen / total * arch["router_scale"]


def layer_for(arch, qn):
    """-> `layer(params, x (B, T, C), name, (conv, routed))`: one layer."""
    heads, kv_heads, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    eps, theta = arch["rms_eps"], arch["rope_theta"]

    def layer(p, x, name, kind):
        conv, routed = kind
        b, t, c = x.shape
        hq = qn(rms_norm(x, p[f"{name}/norm_in/scale"], eps))
        if conv:
            gate_b, gate_c, xs = jnp.split(qn(hq @ qn(p[f"{name}/conv_in/kernel"])), 3, -1)
            mixed = short_conv(qn(gate_b * xs), p[f"{name}/conv_taps"])
            a = qn(qn(gate_c * mixed) @ qn(p[f"{name}/conv_out/kernel"]))
        else:
            q = qn(hq @ qn(p[f"{name}/q/kernel"])).reshape(b, t, heads, hd)
            k = qn(hq @ qn(p[f"{name}/k/kernel"])).reshape(b, t, kv_heads, hd)
            v = qn(hq @ qn(p[f"{name}/v/kernel"])).reshape(b, t, kv_heads, hd)
            q = qn(rms_norm(q, p[f"{name}/q_head_norm/scale"], eps))
            k = qn(rms_norm(k, p[f"{name}/k_head_norm/scale"], eps))
            q, k = qn(rotary(q, theta)), qn(rotary(k, theta))
            a = qn(masked_attention(q, k, v, 0, qn))
            a = qn(a.reshape(b, t, -1) @ qn(p[f"{name}/o/kernel"]))
        x = qn(x + a)
        u32 = rms_norm(x, p[f"{name}/norm_post/scale"], eps)
        u = qn(u32).reshape(b * t, c)
        if not routed:
            y = gated_mlp(u, p[f"{name}/ffn_gate/kernel"], p[f"{name}/ffn_up/kernel"],
                          p[f"{name}/ffn_down/kernel"], qn)
            return qn(x + y.reshape(b, t, c))
        idx, weight = route(u32.reshape(b * t, c) @ p[f"{name}/router"],
                            p[f"{name}/router_bias"], arch)
        y = held_experts(u, idx, weight, p[f"{name}/w_gate"], p[f"{name}/w_up"],
                         p[f"{name}/w_down"], arch, qn)
        return qn(x + y.reshape(b, t, c))

    return layer


def hidden_for(arch, qn):
    """-> `f(params, tokens)`: the final-normed states (B, T, C)."""
    layer = layer_for(arch, qn)

    def hidden(p, tokens):
        x = qn(p["embed/embedding"][tokens])
        for name, conv, routed in layer_kinds(arch):
            x = by_rows(layer, p, x, name, (conv, routed))
        return qn(rms_norm(x, p["norm_final/scale"], arch["rms_eps"]))

    return hidden


def logits_for(arch):
    """-> `f(params, tokens)`: float32 logits (B, T, V), whole — for small
    sizes (the test against published modelling code)."""
    hidden = hidden_for(arch, quantizer("float32"))

    def logits(p, tokens):
        with jax.default_matmul_precision("highest"):
            return hidden(p, tokens) @ p["embed/embedding"].T

    return logits


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens (B, T) i32, targets (B, T) i32)`: the mean
    next-token cross-entropy through the tied head. Below float32, `qn`
    rounds what a computation in that precision would hold in it: every
    matmul's operands, the gate products and every layer's output; the norms'
    and the taps' arithmetic, the router (its scores, choice and gates), the
    softmaxes and the loss stay float32."""
    qn = quantizer(precision)
    hidden = hidden_for(arch, qn)

    def loss(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            x = hidden(p, tokens)
            n = targets.size
            block = min(HEAD_BLOCK, n)
            assert n % block == 0, (n, block)
            head = qn(p["embed/embedding"]).T

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
