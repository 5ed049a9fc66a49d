"""Plain Ling-3.0-flash decoder (inclusionAI, `config.json`, model_type
`bailing_hybrid`; config keys in brackets): the forward pass, its mean
next-token cross-entropy and, through `jax.grad`, its gradients.
Straightforward `jax.numpy`, float32, no flax, no kernel, no chunking of the
recurrence, no sorting or grouping of tokens. Imports nothing from the
program under test. The linear-attention layer is Kimi delta attention (KDA;
Kimi Linear, arXiv:2510.26692 §3) written token by token; the latent
attention and the router are DeepSeek-V3's (arXiv:2412.19437 §2.1-2.2), the
first without its query bottleneck, the second with its group limit.

`arch`: {"vocab_size", "hidden_size", "num_layers" [num_hidden_layers],
"num_heads", "head_dim" [head_dim = qk_nope_head_dim: also KDA's key and
value width], "rope_dim" [qk_rope_head_dim], "v_head_dim", "q_rank"
[q_lora_rank: null = 0], "kv_rank" [kv_lora_rank], "kda_layout" [1 = a KDA
layer, 0 = the latent attention; layer_group_size 6: five and one; a list
repeated to the depth], "conv_kernel" [short_conv_kernel_size],
"kda_lower_bound", "out_gate" [gated_attention_proj_granularity_type
head_wise], "dense_layers" [first_k_dense_replace], "dense_width"
[intermediate_size], "expert_width" [moe_intermediate_size], "num_experts"
[the router's width], "experts_held", "first_expert", "top_k"
[num_experts_per_tok], "n_group", "topk_group", "shared_experts"
[num_shared_experts], "router_scale" [routed_scaling_factor], "rope_theta",
"rms_eps", "seq_len"}.

One layer, x (T, C), H heads, d = head_dim, every projection without bias:

    h = RMSNorm(x)
    kda_layout[i] = 1:
        q~, k~, v~ = h W_q, h W_k, h W_v                    C -> H d each
        q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
                  depthwise, causal, `conv_kernel` taps, zeros before the row
                  [linear_silu]
        q, k    = q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6)   per head
                  [use_qk_norm]
        g_t     = kda_lower_bound * sigmoid(exp(A_log) * (h_t W_f + dt_bias))
                  in (lower, 0)^d: the log of the per-CHANNEL decay; W_f of
                  full rank [no_kda_lora], A_log one a head [kda_safe_gate]
        beta_t  = sigmoid(h_t w_beta)                       one a head
        S_t     = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
                  S (d, d) a head, S = 0 at the row's start
        o_t     = S_t^T q_t / sqrt(d)
        a_t     = [RMSNorm_d(o_t) * sigmoid(h_t w_g)] over the heads, W_o
                  [group_norm_size 1: one scale of d for all heads; one gate
                  a head]; no rotary embedding
    kda_layout[i] = 0:
        q = h W_q -> H x [q_nope d | q_rope]                no bottleneck
        [c | kr] = h W_kva;  [k_nope | v] per head = RMSNorm(c) W_kvb
        q_rope, kr <- rotary(theta, pairs (2i, 2i+1) [rope_interleave])
        a = softmax_causal((q_nope k_nope^T + q_rope kr^T) / sqrt(d + rope_dim)) v
        a = [a * sigmoid(h w_g)] over the heads, W_o        with out_gate
    x1 = x + a;  u = RMSNorm(x1)
    layers < dense_layers:  x2 = x1 + W_down(silu(W_gate u) * W_up u)
    the others:  s = sigmoid(u W_r), float32;  c = s + b
                 the experts in n_group groups of E / n_group; a group's
                 score = the sum of its two largest c; only the topk_group
                 best groups stay; chosen = the top_k of c inside them
                 g_e = router_scale * s_e / sum over the chosen of s
                 x2 = x1 + sum over the chosen of g_e E_e(u) + E_shared(u)

then the final RMSNorm and an untied head. `b` steers the choice and nothing
else: its gradient is exactly zero.

The chip's share (model-configs guide, section 4): only experts
`first_expert .. first_expert + experts_held - 1` exist here. The router
keeps its full width, its groups and its top_k; a chosen expert that is not
held adds nothing. The shared expert is held by every chip. `vocab_size` is
the slice of the vocabulary held here.

Departures / assumptions, the program's too (the configuration's `assumed`):
- [use_qk_norm] is read as KDA's L2 norm; the latent layer keeps
  DeepSeek-V3's norm of its latent and has no norm on q (there is no q
  latent to norm);
- the taps are stored (L, H d), the published `Conv1d` weight (H d, 1, L)
  with its axes swapped;
- a masked group's experts read -inf (DeepSeek-V3's released inference code;
  `transformers`' port fills 0.0, which differs only where a kept expert's
  s + b is negative);
- [expert_swiglu_limit_list], [share_expert_swiglu_limit_list] are 0 in every
  layer kept: no clamp. The multi-token-prediction module is not built
  ([mtp_loss_scaling_factor] 0: its loss and gradient are nothing);
- `b` stays as seeded; no balance loss ([seq_aux] is the recipe's);
- packed rows carry the KDA state, mix (the taps) and attend across document
  boundaries.

How it fits: 822 M float32 parameters with their gradient and Adam's two
moments are 13.2 GB of a chip's 16.9, so the step's temporaries have to stay
under 3.7 GB. Every layer walks the rows of the batch one at a time, each
row a `jax.checkpoint`; the recurrence walks the row in blocks of
RECURRENCE_BLOCK tokens, each block a `jax.checkpoint` (the state at every
block's start is all that stands of it), both mixers take the heads a group
at a time, each group rematerialized, attention walks the queries in blocks,
the dense MLP the tokens, every HELD expert is applied to EVERY token under its
masked gate, and the head takes the rows in blocks. None of that changes a
value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels, taps (fan-in L) and expert banks, N(0, 0.02)
embedding, norm scales 1, b ~ N(0, 0.1), A_log ~ N(0, 0.5) and dt_bias = -4:
exp(g) a token then lies between about 0.78 and 0.97 a channel, so that the
state reaches over chunks of the program's chunked form and a step without
its decay can be told apart.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .joyai_llm_flash import (
    BIAS_STD,
    HEAD_BLOCK,
    HEAD_GROUP,
    by_rows,
    causal_attention,
    gated_mlp,
    held_experts,
    rotary_interleaved,
)
from .lfm2_8b_a1b import short_conv
from .smallthinker import quantizer, rms_norm

RECURRENCE_BLOCK = 64   # tokens per rematerialized block of the recurrence
MLP_BLOCK = 2048        # tokens per rematerialized block of the dense MLP
KDA_HEAD_GROUP = 8      # heads that go through the recurrence together (16: 4.4 GB of temporaries, over what is free)
A_LOG_STD, DT_BIAS = 0.5, -4.0


def layer_kinds(arch):
    """(name, kda?, routed?) of every layer."""
    which = arch["kda_layout"]
    return [(f"layer{i}", bool(which[i % len(which)]), i >= arch["dense_layers"])
            for i in range(arch["num_layers"])]


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, heads = arch["hidden_size"], arch["num_heads"]
    hd, dr, dv = arch["head_dim"], arch["rope_dim"], arch["v_head_dim"]
    held, width, taps = arch["experts_held"], arch["expert_width"], arch["conv_kernel"]

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    def ones(name, n):
        spec[name] = ((n,), "ones", 0.0)

    def gated(prefix, w):
        normal(f"{prefix}_gate/kernel", (c, w), c)
        normal(f"{prefix}_up/kernel", (c, w), c)
        normal(f"{prefix}_down/kernel", (w, c), w)

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for b, kda, routed in layer_kinds(arch):
        ones(f"{b}/norm_in/scale", c)
        if kda:
            for n in "qkv":
                normal(f"{b}/kda_{n}/kernel", (c, heads * hd), c)
                normal(f"{b}/kda_taps_{n}", (taps, heads * hd), taps)
            normal(f"{b}/kda_f/kernel", (c, heads * hd), c)
            spec[f"{b}/kda_a_log"] = ((heads,), "normal", A_LOG_STD)
            spec[f"{b}/kda_dt_bias"] = ((heads * hd,), "const", DT_BIAS)
            normal(f"{b}/kda_beta/kernel", (c, heads), c)
            normal(f"{b}/kda_gate/kernel", (c, heads), c)
            ones(f"{b}/kda_norm/scale", hd)
            normal(f"{b}/kda_o/kernel", (heads * hd, c), heads * hd)
        else:
            normal(f"{b}/q/kernel", (c, heads * (hd + dr)), c)
            normal(f"{b}/kv_a/kernel", (c, arch["kv_rank"] + dr), c)
            ones(f"{b}/kv_norm/scale", arch["kv_rank"])
            normal(f"{b}/kv_b/kernel", (arch["kv_rank"], heads * (hd + dv)),
                   arch["kv_rank"])
            if arch["out_gate"]:
                normal(f"{b}/o_gate/kernel", (c, heads), c)
            normal(f"{b}/o/kernel", (heads * dv, c), heads * dv)
        ones(f"{b}/norm_post/scale", c)
        if not routed:
            gated(f"{b}/ffn", arch["dense_width"])
            continue
        normal(f"{b}/router", (c, arch["num_experts"]), c)
        spec[f"{b}/router_bias"] = ((arch["num_experts"],), "normal", BIAS_STD)
        normal(f"{b}/w_gate", (held, c, width), c)
        normal(f"{b}/w_up", (held, c, width), c)
        normal(f"{b}/w_down", (held, width, c), width)
        if arch["shared_experts"]:
            gated(f"{b}/shared", arch["shared_experts"] * width)
    ones("norm_final/scale", c)
    normal("lm_head/kernel", (c, arch["vocab_size"]), c)
    return spec


def kda_recurrence(q, k, v, g, beta):
    """q, k, g (B, T, H, d_k), v (B, T, H, d_v), beta (B, T, H) -> o
    (B, T, H, d_v) with o_t = S_t^T q_t, the state walked token by token:

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    """
    b, t, h, dk = k.shape
    block = min(RECURRENCE_BLOCK, t)
    assert t % block == 0, (t, block)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state                 # Diag(exp g) S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(x):   # (B, T, ...) -> (T / block, block, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // block, block, *x.shape[1:])

    _, o = jax.lax.scan(tokens, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1)


def route(logits, bias, arch):
    """(N, E) float32 logits -> (chosen ids (N, k), their weights (N, k)),
    the group limit by plain sorting."""
    s = jax.nn.sigmoid(logits)
    biased = s + bias
    n, e = s.shape
    groups = arch["n_group"]
    if groups > 1:
        per = biased.reshape(n, groups, e // groups)
        score = jnp.sort(per, axis=-1)[..., -2:].sum(axis=-1)        # (N, groups)
        best = jnp.argsort(-score, axis=-1)[:, :arch["topk_group"]]
        keep = jnp.zeros((n, groups), bool).at[jnp.arange(n)[:, None], best].set(True)
        biased = jnp.where(keep[:, :, None], per, -jnp.inf).reshape(n, e)
    idx = jnp.argsort(-biased, axis=-1)[:, :arch["top_k"]]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, arch["router_scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def head_groups(heads: int, group: int = HEAD_GROUP):
    """(groups, heads a group): the mixers walk the heads a group at a time."""
    n = heads // group if heads % group == 0 else 1
    return n, heads // n


def kda_mix(p, hq, name, arch, qn):
    """The KDA layer's token mixer on the normed input hq (B, T, C). Every
    step of it is per head or per channel, and a W_o is the sum over groups
    of heads of a_g W_o[g]: the heads go through it a group at a time, each
    group rematerialized, so that one group's q, k, v, g and states are all
    that stands."""
    b, t, c = hq.shape
    heads, hd = arch["num_heads"], arch["head_dim"]
    n, per = head_groups(heads, KDA_HEAD_GROUP)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def heads_part(w_q, w_k, w_v, taps_q, taps_k, taps_v, w_f, dt_bias, a_log,
                   w_beta, w_gate, w_o):
        def branch(w, taps):
            x = jax.nn.silu(short_conv(qn(hq @ qn(w)), taps))
            return x.reshape(b, t, per, hd)

        q, k, v = branch(w_q, taps_q), branch(w_k, taps_k), branch(w_v, taps_v)
        f = (qn(hq @ qn(w_f)) + dt_bias).reshape(b, t, per, hd)
        g = arch["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * f)
        beta = jax.nn.sigmoid(qn(hq @ qn(w_beta)))
        o = kda_recurrence(qn(unit(q)), qn(unit(k)), qn(v), g, beta) / math.sqrt(hd)
        gate = jax.nn.sigmoid(qn(hq @ qn(w_gate)))
        o = rms_norm(o, p[f"{name}/kda_norm/scale"], arch["rms_eps"]) * gate[..., None]
        return qn(o.reshape(b, t, -1)) @ qn(w_o)

    def columns(w):     # (rows, heads x d) -> (n, rows, per x d)
        return w.reshape(w.shape[0], n, -1).transpose(1, 0, 2)

    a, _ = jax.lax.scan(
        lambda acc, ws: (acc + heads_part(*ws), None), jnp.zeros_like(hq),
        tuple(columns(p[f"{name}/kda_{x}/kernel"]) for x in "qkv")
        + tuple(columns(p[f"{name}/kda_taps_{x}"]) for x in "qkv")
        + (columns(p[f"{name}/kda_f/kernel"]),
           p[f"{name}/kda_dt_bias"].reshape(n, per * hd),
           p[f"{name}/kda_a_log"].reshape(n, per),
           columns(p[f"{name}/kda_beta/kernel"]),
           columns(p[f"{name}/kda_gate/kernel"]),
           p[f"{name}/kda_o/kernel"].reshape(n, per * hd, c)))
    return qn(a)


def mla_mix(p, hq, name, arch, qn):
    """The latent attention without a query bottleneck on hq (B, T, C)."""
    b, t, c = hq.shape
    heads, hd, dr = arch["num_heads"], arch["head_dim"], arch["rope_dim"]
    dv, eps, theta = arch["v_head_dim"], arch["rms_eps"], arch["rope_theta"]
    kv = qn(hq @ qn(p[f"{name}/kv_a/kernel"]))
    ckv = qn(rms_norm(kv[..., :arch["kv_rank"]], p[f"{name}/kv_norm/scale"], eps))
    k_rope = qn(rotary_interleaved(kv[..., arch["kv_rank"]:].reshape(b, t, 1, dr), theta))
    gate = (jax.nn.sigmoid(qn(hq @ qn(p[f"{name}/o_gate/kernel"])))
            if arch["out_gate"] else jnp.ones((b, t, heads), jnp.float32))

    # a W_o = the sum over groups of heads of a_g W_o[g]: one group's q, k, v
    # is all that stands
    n, per = head_groups(heads)

    def grouped(w, last):   # (rank, heads x last) -> (n, rank, per, last)
        return w.reshape(w.shape[0], n, per, last).transpose(1, 0, 2, 3)

    @jax.checkpoint
    def heads_part(w_q, w_kvb, w_o, gate_g):
        q = qn(jnp.einsum("btc,chd->bthd", hq, qn(w_q)))
        kv_b = qn(jnp.einsum("btr,rhd->bthd", ckv, qn(w_kvb)))
        q_rope = qn(rotary_interleaved(q[..., hd:], theta))
        a = qn(causal_attention(q[..., :hd], kv_b[..., :hd], q_rope, k_rope,
                                kv_b[..., hd:], qn))
        return qn(a * gate_g[..., None]).reshape(b, t, -1) @ qn(w_o)

    a, _ = jax.lax.scan(
        lambda acc, ws: (acc + heads_part(*ws), None), jnp.zeros_like(hq),
        (grouped(p[f"{name}/q/kernel"], hd + dr),
         grouped(p[f"{name}/kv_b/kernel"], hd + dv),
         p[f"{name}/o/kernel"].reshape(n, per * dv, c),
         gate.reshape(b, t, n, per).transpose(2, 0, 1, 3)))
    return qn(a)


def layer_for(arch, qn):
    """-> `layer(params, x (B, T, C), name, (kda, routed))`: one layer."""

    def layer(p, x, name, kind):
        kda, routed = kind
        b, t, c = x.shape
        hq = qn(rms_norm(x, p[f"{name}/norm_in/scale"], arch["rms_eps"]))
        x = qn(x + (kda_mix if kda else mla_mix)(p, hq, name, arch, qn))
        u32 = rms_norm(x, p[f"{name}/norm_post/scale"], arch["rms_eps"])
        u = qn(u32).reshape(b * t, c)
        if not routed:
            # the 6,144-wide MLP in blocks of tokens, each rematerialized
            blocks = b * t // min(MLP_BLOCK, b * t)
            y = jax.lax.map(jax.checkpoint(lambda ub: gated_mlp(
                ub, p[f"{name}/ffn_gate/kernel"], p[f"{name}/ffn_up/kernel"],
                p[f"{name}/ffn_down/kernel"], qn)), u.reshape(blocks, -1, c))
            return qn(x + y.reshape(b, t, c))
        idx, weight = route(u32.reshape(b * t, c) @ p[f"{name}/router"],
                            p[f"{name}/router_bias"], arch)
        y = held_experts(u, idx, weight, p[f"{name}/w_gate"], p[f"{name}/w_up"],
                         p[f"{name}/w_down"], arch, qn)
        if arch["shared_experts"]:
            y = y + gated_mlp(u, p[f"{name}/shared_gate/kernel"],
                              p[f"{name}/shared_up/kernel"],
                              p[f"{name}/shared_down/kernel"], qn)
        return qn(x + y.reshape(b, t, c))

    return layer


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens (B, T) i32, targets (B, T) i32)`: the mean
    next-token cross-entropy. Below float32, `qn` rounds what a computation
    in that precision would hold in it: every matmul's operands, q, k and v
    as the recurrence reads them, and every layer's output; the norms', the
    taps', the decay's and the recurrence's arithmetic, the router (its
    scores, choice and gates), the softmaxes and the loss stay float32."""
    qn = quantizer(precision)
    layer = layer_for(arch, qn)

    def loss(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            x = qn(p["embed/embedding"][tokens])
            for name, kda, routed in layer_kinds(arch):
                x = by_rows(layer, p, x, name, (kda, routed))
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
