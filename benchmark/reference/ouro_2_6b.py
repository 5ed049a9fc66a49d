"""Plain Ouro-2.6B looped decoder (ByteDance, `config.json`, model_type
`ouro`; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741 section 3 and the released `modeling_ouro.py`; config keys
in brackets): the forward pass over the `loops` passes, the first-stage
training objective and, through `jax.grad`, its gradients. Straightforward
`jax.numpy`, float32, no flax, no kernel, no scan over the passes. Imports
nothing from the program under test.

`arch`: {"vocab_size", "hidden_size", "num_layers" [num_hidden_layers],
"num_heads" [num_attention_heads], "num_kv_heads" [num_key_value_heads],
"head_dim", "dense_width" [intermediate_size], "rope_theta", "rms_eps"
[rms_norm_eps], "loops" [total_ut_steps], "exit_beta", "seq_len"}.

With tokens (B, T), x (B, T, C), H heads of d = head_dim, no bias anywhere
but the gate's, R = loops:

    h(0) = Emb(tokens)
    for t = 1 .. R:                          the SAME leaves at every t
        x = h(t-1)
        for l = 1 .. L:
            a = Attn_l(RMSNorm_l,1(x))       q, k, v = u W_q, u W_k, u W_v;
                                             rotary over the whole head (i
                                             with i + d/2, positions 0..T-1
                                             in every pass); softmax(q k^T /
                                             sqrt(d)) v on the causal
                                             triangle; W_o
            x = x + RMSNorm_l,2(a)           the sandwich: a norm on the
                                             sub-layer's OUTPUT too
            m = W_down(SiLU(W_gate u) * W_up u),  u = RMSNorm_l,3(x)
            x = x + RMSNorm_l,4(m)
        h(t) = RMSNorm_f(x)                  closes EVERY pass: what the head
                                             and the gate read AND what pass
                                             t + 1 starts from
        logits(t) = h(t) W_head              one untied head for all passes
        lambda(t) = sigmoid(h(t) . w_g + b_g)   the exit gate, one unit

    S(0) = 1,  S(t) = S(t-1) (1 - lambda(t))
    p(t) = lambda(t) S(t-1) for t < R,  p(R) = S(R-1)        sums to 1
    loss = mean over the B T targets of
           [ sum_t p(t) CE(logits(t), target) - beta H(p) ]
    H(p) = - sum_t p(t) log max(p(t), 1e-9)

with the gradient through p as well as through the logits.

Departures / assumptions, the program's too (the configuration's `assumed`
gives each its source):
- beta = `exit_beta` 0.05 (the paper lowers it from 0.1; the config has no
  key); the logarithm's argument clamped at 1e-9;
- the gate reads the NORMED states, as the head does;
- not built: adaptive exit at inference (`early_exit_threshold` 1 serves the
  last pass, which is what evaluation reads), the paper's second stage (the
  gate trained alone against the per-pass improvement, the model frozen),
  the KV cache of R x L slots;
- packed rows attend across document boundaries.

How it fits: 407 M float32 parameters with their gradient and Adam's two
moments are 6.5 GB of a chip's 16.9. Each of the R x L layer applications is
a `jax.checkpoint` (its float32 input, 67 MB a row of 8,192, is what stands),
attention walks the queries in blocks, and the R x B x T rows go through the
head in blocks that hand back each row's cross-entropy. None of that changes
a value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels (the gate's too: N(0, 1/sqrt(C)), so that the
lambda spread over (0, 1) and every pass carries weight), N(0, 0.02)
embedding, norm scales 1, the gate's bias 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .joyai_llm_flash import gated_mlp
from .smallthinker import masked_attention, quantizer, rms_norm, rotary

HEAD_BLOCK = 1024   # rows per block of the head and its loss
LOG_FLOOR = 1e-9    # the entropy's logarithm reads max(p, this)


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, hd, width = arch["hidden_size"], arch["head_dim"], arch["dense_width"]

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    def ones(name, n):
        spec[name] = ((n,), "ones", 0.0)

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for i in range(arch["num_layers"]):
        b = f"layer{i}"
        ones(f"{b}/norm_in/scale", c)
        normal(f"{b}/q/kernel", (c, arch["num_heads"] * hd), c)
        normal(f"{b}/k/kernel", (c, arch["num_kv_heads"] * hd), c)
        normal(f"{b}/v/kernel", (c, arch["num_kv_heads"] * hd), c)
        normal(f"{b}/o/kernel", (arch["num_heads"] * hd, c), arch["num_heads"] * hd)
        ones(f"{b}/norm_mix_out/scale", c)
        ones(f"{b}/norm_post/scale", c)
        normal(f"{b}/ffn_gate/kernel", (c, width), c)
        normal(f"{b}/ffn_up/kernel", (c, width), c)
        normal(f"{b}/ffn_down/kernel", (width, c), width)
        ones(f"{b}/norm_ffn_out/scale", c)
    ones("norm_final/scale", c)
    normal("lm_head/kernel", (c, arch["vocab_size"]), c)
    normal("exit_gate/kernel", (c, 1), c)
    spec["exit_gate/bias"] = ((1,), "zeros", 0.0)
    return spec


def layer_for(arch, qn):
    """-> `layer(params, x (B, T, C), name)`: one layer with its four norms."""
    heads, kv_heads, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    eps, theta = arch["rms_eps"], arch["rope_theta"]

    def layer(p, x, name):
        b, t, c = x.shape
        u = qn(rms_norm(x, p[f"{name}/norm_in/scale"], eps))
        q = qn(u @ qn(p[f"{name}/q/kernel"])).reshape(b, t, heads, hd)
        k = qn(u @ qn(p[f"{name}/k/kernel"])).reshape(b, t, kv_heads, hd)
        v = qn(u @ qn(p[f"{name}/v/kernel"])).reshape(b, t, kv_heads, hd)
        q, k = qn(rotary(q, theta)), qn(rotary(k, theta))
        a = qn(masked_attention(q, k, v, 0, qn))
        a = qn(a.reshape(b, t, -1) @ qn(p[f"{name}/o/kernel"]))
        x = qn(x + qn(rms_norm(a, p[f"{name}/norm_mix_out/scale"], eps)))
        u = qn(rms_norm(x, p[f"{name}/norm_post/scale"], eps)).reshape(b * t, c)
        m = gated_mlp(u, p[f"{name}/ffn_gate/kernel"], p[f"{name}/ffn_up/kernel"],
                      p[f"{name}/ffn_down/kernel"], qn).reshape(b, t, c)
        return qn(x + qn(rms_norm(m, p[f"{name}/norm_ffn_out/scale"], eps)))

    return layer


def states_for(arch, qn):
    """-> `f(params, tokens)`: the normed states of the R passes (R, B, T, C)."""
    layer = layer_for(arch, qn)

    def states(p, tokens):
        x = qn(p["embed/embedding"][tokens])
        out = []
        for _ in range(arch["loops"]):
            for i in range(arch["num_layers"]):
                x = jax.checkpoint(layer, static_argnums=2)(p, x, f"layer{i}")
            x = qn(rms_norm(x, p["norm_final/scale"], arch["rms_eps"]))
            out.append(x)
        return jnp.stack(out)

    return states


def exit_distribution(p, states):
    """The gate on the normed states (R, B, T, C) -> p (R, B, T), float32."""
    lam = jax.nn.sigmoid(states @ p["exit_gate/kernel"][:, 0] + p["exit_gate/bias"][0])
    dist, stay = [], jnp.ones_like(lam[0])           # S(0) = 1
    for t in range(lam.shape[0] - 1):
        dist.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(dist + [stay])                  # the last pass: S(R-1)


def row_cross_entropy(x, head, targets):
    """x (N, C), head (C, V), targets (N,) -> each row's cross-entropy (N,),
    the rows in blocks: one block's float32 logits stand at a time."""
    n = targets.shape[0]
    block = min(HEAD_BLOCK, n)
    assert n % block == 0, (n, block)

    @jax.checkpoint
    def rows(xs):
        xb, tb = xs
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    return jax.lax.map(rows, (x.reshape(n // block, block, -1),
                              targets.reshape(n // block, block))).reshape(n)


def loss_parts_for(arch, precision: str = "float32"):
    """-> `f(params, tokens, targets)`: (the objective, each pass's mean
    cross-entropy (R,), the mean of p (R,))."""
    qn = quantizer(precision)
    states = states_for(arch, qn)

    def parts(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            h = states(p, tokens)                           # (R, B, T, C)
            r, n = h.shape[0], targets.size
            dist = exit_distribution(p, h).reshape(r, n)
            ce = row_cross_entropy(
                h.reshape(r * n, -1), qn(p["lm_head/kernel"]),
                jnp.tile(targets.reshape(-1), r)).reshape(r, n)
            entropy = -jnp.sum(dist * jnp.log(jnp.maximum(dist, LOG_FLOOR)), axis=0)
            per_target = jnp.sum(dist * ce, axis=0) - arch["exit_beta"] * entropy
            return jnp.mean(per_target), jnp.mean(ce, axis=1), jnp.mean(dist, axis=1)

    return parts


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens (B, T) i32, targets (B, T) i32)`: the
    objective above. Below float32, `qn` rounds what a computation in that
    precision would hold in it: every matmul's operands, the gate products
    and every sub-layer's and pass's output; the norms' arithmetic, the exit
    gate and its distribution, the softmaxes and the loss stay float32."""
    parts = loss_parts_for(arch, precision)
    return lambda p, tokens, targets: parts(p, tokens, targets)[0]
