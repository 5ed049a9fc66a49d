"""Plain Olmo-Hybrid-7B decoder (allenai, `config.json`, model_type
`olmo_hybrid`; config keys in brackets): three Gated DeltaNet layers
(arXiv:2412.06464, as flash-linear-attention's `GatedDeltaNet` layer writes
it) to one full-attention layer, dense SwiGLU, Olmo 3's reordered norm. The
forward pass, its mean next-token cross-entropy and, through `jax.grad`, its
gradients. Straightforward `jax.numpy`, float32, no flax, no kernel, no
chunks: the recurrence is walked token by token. Imports nothing from the
program under test.

`arch`: {"vocab_size", "hidden_size", "num_layers" [num_hidden_layers],
"num_heads" [num_attention_heads = linear_num_key_heads =
linear_num_value_heads], "num_kv_heads" [num_key_value_heads], "head_dim"
[hidden_size / num_attention_heads], "gdn_key_dim" [linear_key_head_dim],
"gdn_value_dim" [linear_value_head_dim], "conv_kernel"
[linear_conv_kernel_dim], "gdn_layout" (0/1 a layer, repeated to the depth:
1 = [layer_types] "linear_attention"), "dense_width" [intermediate_size],
"rms_eps" [rms_norm_eps], "heads_held" (0 = all of num_heads), "seq_len"}.

h is a block's input (B, T, C), H heads, d_k = gdn_key_dim, d_v =
gdn_value_dim, no bias anywhere.

Gated DeltaNet layer:

    q' = SiLU(taps_q(h W_q))  k' = SiLU(taps_k(h W_k))  v = SiLU(taps_v(h W_v))
                                     causal depthwise, conv_kernel taps
    q  = q' / ||q'||_2 * d_k^-1/2       k = k' / ||k'||_2           per head
    beta_t = 2 sigmoid(h_t W_b)   in (0, 2) [linear_allow_neg_eigval], one a head
    g_t = -exp(A_log) * softplus(h_t W_a + dt_bias)  <= 0, unbounded, one a head
    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
                                     S (d_k, d_v) a head, 0 at the row's start
    o_t = S_t^T q_t
    y_t = [RMSNorm_{d_v}(o_t; w) * SiLU(h_t W_g)] W_o     w one scale for all heads

Attention layer: q = RMSNorm(h W_q; w_q), k = RMSNorm(h W_k; w_k) over the
WHOLE projection (all heads' dims together), split into heads of head_dim, no
rotary embedding, softmax(q k^T / sqrt(head_dim)) v on the causal triangle,
W_o.

Block, both kinds: x <- x + RMSNorm(Mixer(x)); x <- x + RMSNorm(W_down(
SiLU(W_gate x) * W_up x)): a norm on each sub-layer's OUTPUT and none on its
input. Then a final RMSNorm and an untied head; loss = mean over all
positions of the cross-entropy of the next token.

The chip's share (model-configs guide, section 4): the leaves are those of
`heads_held` of every layer's heads (which ones, `head_share` is told): their
columns of every projection, tap and per-head leaf, their rows of W_o. What
the absent heads would add to a mixer's output is left out, and that partial
sum is what the output norm and the next layer see. The whole-width QK-norm's
mean square runs over the held columns. `head_share` cuts a whole model's
leaves to a share (the tests' tie of the share to the whole). `vocab_size`
is the slice of the vocabulary held here.

Departures / assumptions, the program's too (the configuration's `assumed`
gives each its source):
- no rotary embedding in the attention layers ([rope_parameters.rope_theta]
  null); Olmo 3's reordered norm in BOTH kinds of layer, its QK-norm over the
  whole projection;
- the details of flash-linear-attention's layer the config does not carry:
  separate taps for q, k and v, SiLU after them, the L2 norm (eps 1e-6 inside
  the root) with q scaled by d_k^-1/2, a SiLU gate as wide as o, one
  d_v-wide norm scale, the taps stored (L, H d) (the published `Conv1d`
  weight (H d, 1, L) with its axes swapped);
- packed rows carry the state, mix (the taps) and attend across document
  boundaries.

How it fits: 766 M float32 parameters with their gradient and Adam's two
moments are 12.3 GB of a chip's 16.9, so a step's temporaries have to stay
under 4.6 GB. Every layer takes the rows of the batch one at a time, each row
a `jax.checkpoint`; a Gated DeltaNet mixer takes the heads a group at a
time, each group rematerialized; the recurrence walks the row in blocks of
RECURRENCE_BLOCK tokens, each block a `jax.checkpoint` (the state at every
block's start is all that stands of it), attention walks the queries in
blocks, the SwiGLU its 11,008 columns in MLP_CHUNKS parts (a part's weights
meet every token once, so their gradient is made once and no copy of the
three matrices' gradient is carried along a loop), and the head takes the
rows in blocks. None of that changes a value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels and taps (fan-in L), N(0, 0.02) embedding, norm
scales 1. The harness draws a leaf normal or constant (`common.make_params`),
so flash-linear-attention's A ~ U(1, 16) and dt log-uniform in [1e-3, 0.1]
are met in spread and in median: A_log ~ N(0, 0.675) (the standard deviation
of log U(1, 16)) and dt_bias = softplus^-1(0.0708), so that the median decay
a token exp(A_log) softplus(dt_bias) = 0.0708 is the published
initialisation's (7.08 x 0.01): exp(g) between about 0.8 and 0.98 a token,
the state reaches over the program's chunks.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .lfm2_8b_a1b import short_conv
from .smallthinker import masked_attention, quantizer, rms_norm

RECURRENCE_BLOCK = 64   # tokens per rematerialized block of the recurrence
GDN_HEAD_GROUP = 3      # heads that go through a Gated DeltaNet mixer together (15 at once: 2.9 GB of temporaries a layer, over what is free)
MLP_CHUNKS = 8          # parts of the SwiGLU's width, each rematerialized
HEAD_BLOCK = 1024       # rows per block of the head and its loss
A_LOG_STD = 0.675       # the standard deviation of log U(1, 16)
DT_BIAS = math.log(math.expm1(0.0708))   # softplus^-1 of the median A x dt over A's median 1


def layer_kinds(arch):
    """(name, gdn?) of every layer."""
    which = arch["gdn_layout"]
    return [(f"layer{i}", bool(which[i % len(which)]))
            for i in range(arch["num_layers"])]


def held(arch):
    """(query heads, KV heads) whose leaves stand here."""
    heads = arch["heads_held"] or arch["num_heads"]
    return heads, arch["num_kv_heads"] * heads // arch["num_heads"]


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, hd, width, taps = (arch["hidden_size"], arch["head_dim"], arch["dense_width"],
                          arch["conv_kernel"])
    dk, dv = arch["gdn_key_dim"], arch["gdn_value_dim"]
    heads, kv_heads = held(arch)

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    def ones(name, n):
        spec[name] = ((n,), "ones", 0.0)

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for b, gdn in layer_kinds(arch):
        if gdn:
            for n, d in (("q", dk), ("k", dk), ("v", dv)):
                normal(f"{b}/gdn_{n}/kernel", (c, heads * d), c)
                normal(f"{b}/gdn_taps_{n}", (taps, heads * d), taps)
            normal(f"{b}/gdn_gate/kernel", (c, heads * dv), c)
            normal(f"{b}/gdn_a/kernel", (c, heads), c)
            normal(f"{b}/gdn_beta/kernel", (c, heads), c)
            spec[f"{b}/gdn_a_log"] = ((heads,), "normal", A_LOG_STD)
            spec[f"{b}/gdn_dt_bias"] = ((heads,), "const", DT_BIAS)
            ones(f"{b}/gdn_norm/scale", dv)
            normal(f"{b}/gdn_o/kernel", (heads * dv, c), heads * dv)
        else:
            normal(f"{b}/q/kernel", (c, heads * hd), c)
            normal(f"{b}/k/kernel", (c, kv_heads * hd), c)
            normal(f"{b}/v/kernel", (c, kv_heads * hd), c)
            ones(f"{b}/q_norm/scale", heads * hd)
            ones(f"{b}/k_norm/scale", kv_heads * hd)
            normal(f"{b}/o/kernel", (heads * hd, c), heads * hd)
        ones(f"{b}/norm_mix_out/scale", c)
        normal(f"{b}/ffn_gate/kernel", (c, width), c)
        normal(f"{b}/ffn_up/kernel", (c, width), c)
        normal(f"{b}/ffn_down/kernel", (width, c), width)
        ones(f"{b}/norm_ffn_out/scale", c)
    ones("norm_final/scale", c)
    normal("lm_head/kernel", (c, arch["vocab_size"]), c)
    return spec


def head_share(params, arch, first_head: int, heads_held: int):
    """The leaves of heads `first_head .. first_head + heads_held - 1`, cut
    from `params` of an `arch` that holds every head -> (the share's leaves,
    its arch). Per-head leaves and a projection's columns lie head after
    head; the d_v-wide norm scale, the block norms, the SwiGLU, the tables
    are every share's alike."""
    heads = arch["num_heads"]
    assert not arch["heads_held"] and first_head + heads_held <= heads
    group = heads // arch["num_kv_heads"]
    assert first_head % group == 0 and heads_held % group == 0, "a KV group cut"

    def cut(x, axis, of=heads, first=first_head, n=heads_held):
        d = x.shape[axis] // of
        return jax.lax.slice_in_dim(x, first * d, (first + n) * d, axis=axis)

    out = {}
    for name, x in params.items():
        leaf = name.split("/", 1)[-1]
        if leaf in ("gdn_o/kernel", "o/kernel"):
            x = cut(x, 0)
        elif leaf in ("k/kernel", "v/kernel", "k_norm/scale"):
            x = cut(x, -1, heads // group, first_head // group, heads_held // group)
        elif (leaf.startswith(("gdn_", "q/", "q_norm/")) and leaf != "gdn_norm/scale"):
            x = cut(x, -1)
        out[name] = x
    return out, dict(arch, heads_held=heads_held)


def gdn_recurrence(q, k, v, g, beta):
    """q, k (B, T, H, d_k), v (B, T, H, d_v), g and beta (B, T, H) -> o
    (B, T, H, d_v) with o_t = S_t^T q_t, the state walked token by token:

        S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    """
    b, t, h, dk = k.shape
    block = min(RECURRENCE_BLOCK, t)
    assert t % block == 0, (t, block)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
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


def gdn_mix(p, h, name, arch, qn):
    """The Gated DeltaNet layer's token mixer on the block's input h
    (B, T, C), of the held heads. Every step of it is per head, and a W_o is
    the sum over groups of heads of o_g W_o[g]: the heads go through it
    GDN_HEAD_GROUP at a time, each group rematerialized, so that one group's
    q, k, v, gate and states are all that stands."""
    b, t, c = h.shape
    dk, dv = arch["gdn_key_dim"], arch["gdn_value_dim"]
    heads = p[f"{name}/gdn_a_log"].shape[0]
    n = heads // GDN_HEAD_GROUP if heads % GDN_HEAD_GROUP == 0 else 1

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def heads_part(w_q, w_k, w_v, taps_q, taps_k, taps_v, w_a, w_b, a_log, dt_bias,
                   w_g, w_o):
        def branch(w, taps, d):
            return jax.nn.silu(short_conv(qn(h @ qn(w)), taps)).reshape(b, t, -1, d)

        q, k, v = branch(w_q, taps_q, dk), branch(w_k, taps_k, dk), branch(w_v, taps_v, dv)
        beta = 2.0 * jax.nn.sigmoid(qn(h @ qn(w_b)))
        g = -jnp.exp(a_log) * jax.nn.softplus(qn(h @ qn(w_a)) + dt_bias)
        o = gdn_recurrence(qn(unit(q) / math.sqrt(dk)), qn(unit(k)), qn(v), g, beta)
        gate = jax.nn.silu(qn(h @ qn(w_g))).reshape(o.shape)
        o = rms_norm(o, p[f"{name}/gdn_norm/scale"], arch["rms_eps"]) * gate
        return qn(o.reshape(b, t, -1)) @ qn(w_o)

    def columns(w):     # (rows, heads x d) -> (n, rows, heads / n x d)
        return w.reshape(w.shape[0], n, -1).transpose(1, 0, 2)

    a, _ = jax.lax.scan(
        lambda acc, ws: (acc + heads_part(*ws), None), jnp.zeros_like(h),
        tuple(columns(p[f"{name}/gdn_{x}/kernel"]) for x in "qkv")
        + tuple(columns(p[f"{name}/gdn_taps_{x}"]) for x in "qkv")
        + (columns(p[f"{name}/gdn_a/kernel"]), columns(p[f"{name}/gdn_beta/kernel"]),
           p[f"{name}/gdn_a_log"].reshape(n, -1), p[f"{name}/gdn_dt_bias"].reshape(n, -1),
           columns(p[f"{name}/gdn_gate/kernel"]),
           p[f"{name}/gdn_o/kernel"].reshape(n, -1, c)))
    return qn(a)


def attention_mix(p, h, name, arch, qn):
    """The attention layer's token mixer on h (B, T, C), of the held heads:
    the QK-norm over the whole (held) projection, no position."""
    b, t, _ = h.shape
    hd, eps = arch["head_dim"], arch["rms_eps"]
    q = qn(rms_norm(qn(h @ qn(p[f"{name}/q/kernel"])), p[f"{name}/q_norm/scale"], eps))
    k = qn(rms_norm(qn(h @ qn(p[f"{name}/k/kernel"])), p[f"{name}/k_norm/scale"], eps))
    v = qn(h @ qn(p[f"{name}/v/kernel"]))
    a = qn(masked_attention(q.reshape(b, t, -1, hd), k.reshape(b, t, -1, hd),
                            v.reshape(b, t, -1, hd), 0, qn))
    return qn(a.reshape(b, t, -1) @ qn(p[f"{name}/o/kernel"]))


def swiglu(u, w_gate, w_up, w_down, qn):
    """W_down(SiLU(u W_gate) * u W_up) on u (N, C), the width in MLP_CHUNKS
    parts whose products add up."""
    c, width = w_gate.shape
    n = MLP_CHUNKS if width % MLP_CHUNKS == 0 else 1

    def columns(w):     # (C, width) -> (n, C, width / n)
        return w.reshape(c, n, -1).transpose(1, 0, 2)

    @jax.checkpoint
    def part(acc, ws):
        gate, up, down = ws
        hid = qn(jax.nn.silu(qn(u @ qn(gate))) * qn(u @ qn(up)))
        return acc + hid @ qn(down), None

    y, _ = jax.lax.scan(part, jnp.zeros_like(u),
                        (columns(w_gate), columns(w_up), w_down.reshape(n, -1, c)))
    return qn(y)


def layer_for(arch, qn):
    """-> `layer(params, x (B, T, C), name, gdn)`: one block, its two norms
    on the sub-layers' outputs."""
    eps = arch["rms_eps"]

    def layer(p, x, name, gdn):
        b, t, c = x.shape
        a = (gdn_mix if gdn else attention_mix)(p, qn(x), name, arch, qn)
        x = qn(x + qn(rms_norm(a, p[f"{name}/norm_mix_out/scale"], eps)))
        m = swiglu(qn(x).reshape(b * t, c), p[f"{name}/ffn_gate/kernel"],
                   p[f"{name}/ffn_up/kernel"], p[f"{name}/ffn_down/kernel"], qn)
        return qn(x + qn(rms_norm(m.reshape(b, t, c),
                                  p[f"{name}/norm_ffn_out/scale"], eps)))

    return layer


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens (B, T) i32, targets (B, T) i32)`: the mean
    next-token cross-entropy. Below float32, `qn` rounds what a computation
    in that precision would hold in it: every matmul's operands and outputs,
    q, k and v as the recurrence reads them, and every sub-layer's and
    layer's output; the norms', the taps', the decay's and the recurrence's
    arithmetic, the softmaxes and the loss stay float32."""
    qn = quantizer(precision)
    # one row of one layer at a time, rematerialized in the backward pass
    layer = jax.checkpoint(layer_for(arch, qn), static_argnums=(2, 3))

    def loss(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            x = qn(p["embed/embedding"][tokens])
            for name, gdn in layer_kinds(arch):
                x = jnp.concatenate([layer(p, x[i:i + 1], name, gdn)
                                     for i in range(x.shape[0])])
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
