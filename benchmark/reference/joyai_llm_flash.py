"""Plain JoyAI-LLM-Flash decoder (jdopensource, `config.json`; every key of it
is a key of the DeepSeek-V3 architecture, arXiv:2412.19437 §2.1-2.2, whose
equations these are; config keys in brackets): the forward pass, its loss
with the multi-token-prediction term (eq. 21-25) and, through `jax.grad`, its
gradients. Straightforward `jax.numpy`, float32, no flax, no kernel, no
sorting or grouping of tokens. Imports nothing from the program under test.

`arch`: {"vocab_size", "hidden_size", "num_layers", "num_heads", "head_dim"
[qk_nope_head_dim], "rope_dim" [qk_rope_head_dim], "v_head_dim", "q_rank"
[q_lora_rank], "kv_rank" [kv_lora_rank], "dense_layers"
[first_k_dense_replace], "dense_width" [intermediate_size], "expert_width"
[moe_intermediate_size], "num_experts" [n_routed_experts, the router's
width], "experts_held", "first_expert", "top_k" [num_experts_per_tok],
"shared_experts" [n_shared_experts], "router_scale" [routed_scaling_factor],
"rope_theta", "rms_eps", "mtp_layers" [num_nextn_predict_layers],
"mtp_weight", "seq_len"}.

One layer, x (T, C), every projection without bias [attention_bias false]:

    h        = RMSNorm(x)
    c_q      = RMSNorm(h W_qa);   q = c_q W_qb -> H heads x [q_nope | q_rope]
    [c | kr] = h W_kva                         kr is ONE head of rope_dim
    [k_nope | v] per head = RMSNorm(c) W_kvb
    q_rope, kr <- rotary(theta, pairs (2i, 2i+1) [rope_interleave], positions
                  0..T-1); every head's key reads the same kr
    a        = softmax_causal((q_nope k_nope^T + q_rope kr^T)
                              / sqrt(head_dim + rope_dim)) v
    x1       = x + a W_o
    u        = RMSNorm(x1)
    layers < dense_layers:  x2 = x1 + W_down(silu(W_gate u) * W_up u)
    the others:  s = sigmoid(u W_r) [scoring_func], float32
                 chosen = the top_k of s + b [topk_method noaux_tc; n_group =
                          topk_group = 1: no group limit]
                 g_e = router_scale * s_e / sum over the chosen of s
                       [norm_topk_prob true]
                 x2 = x1 + sum over the chosen of g_e E_e(u) + E_shared(u)
                 E: W_down(silu(W_gate u) * W_up u)

then the final RMSNorm and an untied head. `b` steers the choice and nothing
else: its gradient is exactly zero.

Multi-token prediction, depth 1, with hL the last layer's output BEFORE the
final norm and `targets` the row shifted by one:

    h'  = [RMSNorm(hL_i) ; RMSNorm(Emb(targets_i))] W_eh    (eq. 21; Emb shared)
    h'' = one more layer of the second kind, its own weights      (eq. 22)
    loss_mtp = mean over i = 0..T-2 of CE(Head(RMSNorm(h''_i)), targets_{i+1})
    loss = loss_main + mtp_weight * loss_mtp                (eq. 23-25)

over all T positions with the last one weighted 0: under causal attention
position T-1 reaches no other position's loss.

The chip's share (model-configs guide, section 4): only experts
`first_expert .. first_expert + experts_held - 1` exist here. The router
keeps its full width and its top_k; a chosen expert that is not held adds
nothing, and that partial result is what goes on. The shared expert is held
by every chip. `vocab_size` is the slice of the vocabulary held here.

Departures / assumptions, the program's too:
- eq. 21's order [h ; emb] and the un-normed hL are the paper's; the released
  DeepSeek-V3 inference code joins [emb ; h]. With seeded random W_eh the two
  are the same model up to a permutation of W_eh's rows;
- mtp_weight and the absence of a balance loss are the recipe's, not the
  config's; `b` stays as seeded (the rule that moves it from the step's load
  counts is no gradient and not part of the loss);
- packed rows attend across document boundaries.

How it fits: 680 M float32 parameters with their gradient and Adam's two
moments are 10.9 GB of a chip's 16.9, so the step's temporaries have to stay
under 5.6 GB. Every layer walks the rows of the batch ONE AT A TIME, each row
a `jax.checkpoint` (rows do not see each other: attention is within a row,
the experts are per token), attention takes the heads a group at a time and
walks the queries in blocks (one block's (heads, q, T) scores at a time),
every HELD expert is applied to EVERY
token under its masked gate inside a scan whose expert is rematerialized, and
the head takes the rows in blocks. None of that changes a value.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels and expert banks, N(0, 0.02) embedding, norm
scales 1, and b ~ N(0, 0.1): non-zero, so that choosing by s + b and
weighting by s can be told apart (at b = 0 a step that ignores b is right).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .smallthinker import quantizer, rms_norm

QUERY_BLOCK = 128   # queries per attention block
HEAD_GROUP = 8      # heads that go through attention together
HEAD_BLOCK = 1024   # rows per block of the head and its loss
BIAS_STD = 0.1      # the seeded router bias (see above)


def layer_names(arch):
    """(name, routed?) of every layer, the prediction module's last."""
    out = [(f"layer{i}", i >= arch["dense_layers"]) for i in range(arch["num_layers"])]
    return out + [("mtp/layer", True)] * arch["mtp_layers"]


def param_spec(arch) -> Spec:
    spec: Spec = {}
    c, heads = arch["hidden_size"], arch["num_heads"]
    hd, dr, dv = arch["head_dim"], arch["rope_dim"], arch["v_head_dim"]
    held, width = arch["experts_held"], arch["expert_width"]

    def normal(name, shape, fan_in):
        spec[name] = (tuple(shape), "normal", 1.0 / math.sqrt(fan_in))

    def ones(name, n):
        spec[name] = ((n,), "ones", 0.0)

    def gated(prefix, w):
        normal(f"{prefix}_gate/kernel", (c, w), c)
        normal(f"{prefix}_up/kernel", (c, w), c)
        normal(f"{prefix}_down/kernel", (w, c), w)

    spec["embed/embedding"] = ((arch["vocab_size"], c), "normal", 0.02)
    for b, routed in layer_names(arch):
        ones(f"{b}/norm_in/scale", c)
        normal(f"{b}/q_a/kernel", (c, arch["q_rank"]), c)
        ones(f"{b}/q_norm/scale", arch["q_rank"])
        normal(f"{b}/q_b/kernel", (arch["q_rank"], heads * (hd + dr)), arch["q_rank"])
        normal(f"{b}/kv_a/kernel", (c, arch["kv_rank"] + dr), c)
        ones(f"{b}/kv_norm/scale", arch["kv_rank"])
        normal(f"{b}/kv_b/kernel", (arch["kv_rank"], heads * (hd + dv)), arch["kv_rank"])
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
    if arch["mtp_layers"]:
        ones("mtp/norm_h/scale", c)
        ones("mtp/norm_e/scale", c)
        normal("mtp/proj/kernel", (2 * c, c), 2 * c)
        ones("mtp/norm_final/scale", c)
    return spec


def rotary_interleaved(x, theta):
    """x (B, T, H, D): dimension 2i paired with 2i + 1, in place."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def causal_attention(q, k, q_rope, k_rope, v, qn):
    """q, k (B, T, H, D), q_rope (B, T, H, Dr), k_rope (B, T, 1, Dr: the one
    head every query head reads), v (B, T, H, Dv) -> (B, T, H, Dv): scores
    (q k^T + q_rope k_rope^T) / sqrt(D + Dr), key j visible to query i iff
    j <= i; masks from iota; queries in blocks."""
    b, t, h, d = q.shape
    dr = q_rope.shape[-1]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)

    def blocks(x):
        return x.reshape(b, t // block, block, h, -1).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(start, qb, qrb):
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn(qb), qn(k))
             + jnp.einsum("bqhd,bkd->bhqk", qn(qrb), qn(k_rope[:, :, 0]))
             ) / math.sqrt(d + dr)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (block, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block, t), 1)
        p = jax.nn.softmax(jnp.where(cols <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", qn(p), qn(v))

    out = jax.lax.map(lambda xs: one(*xs), (jnp.arange(t // block) * block,
                                            blocks(q), blocks(q_rope)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def gated_mlp(u, w_gate, w_up, w_down, qn):
    hid = qn(jax.nn.silu(qn(qn(u) @ qn(w_gate))) * qn(qn(u) @ qn(w_up)))
    return qn(hid @ qn(w_down))


def route(logits, bias, arch):
    """(N, E) float32 logits -> (chosen ids (N, k), their weights (N, k))."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, arch["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, arch["router_scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def held_experts(u, idx, weight, w_gate, w_up, w_down, arch, qn):
    """u (N, C) -> the held experts' part of the mixture (N, C): each held
    expert on every token, times the token's gate for it (0 where the token
    did not choose it)."""
    first = arch["first_expert"]

    @jax.checkpoint
    def part(e, wg, wu, wd):
        gate = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        return gate[:, None] * gated_mlp(u, wg, wu, wd, qn)

    # only the expert's part is rematerialized: a checkpoint around the whole
    # step would keep the running sum (N, C) of every step for the backward
    # pass, which the sum does not need
    def one(y, xs):
        return y + part(*xs), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def layer_for(arch, qn):
    """-> `layer(params, x (B, T, C), name, routed)`: one layer as above."""
    heads, hd, dr = arch["num_heads"], arch["head_dim"], arch["rope_dim"]
    dv, eps, theta = arch["v_head_dim"], arch["rms_eps"], arch["rope_theta"]

    def layer(p, x, name, routed):
        b, t, c = x.shape
        hq = qn(rms_norm(x, p[f"{name}/norm_in/scale"], eps))
        cq = qn(rms_norm(qn(hq @ qn(p[f"{name}/q_a/kernel"])),
                         p[f"{name}/q_norm/scale"], eps))
        kv = qn(hq @ qn(p[f"{name}/kv_a/kernel"]))
        ckv = qn(rms_norm(kv[..., :arch["kv_rank"]], p[f"{name}/kv_norm/scale"], eps))
        k_rope = qn(rotary_interleaved(
            kv[..., arch["kv_rank"]:].reshape(b, t, 1, dr), theta))

        # a W_o = the sum over groups of heads of a_g W_o[g]: the heads go
        # through W_qb, W_kvb, the scores and W_o a group at a time, each
        # group rematerialized, so that one group's q, k, v is all that stands
        n = heads // HEAD_GROUP if heads % HEAD_GROUP == 0 else 1
        per = heads // n

        def grouped(w, last):   # (rank, heads x last) -> (n, rank, per, last)
            return w.reshape(w.shape[0], n, per, last).transpose(1, 0, 2, 3)

        @jax.checkpoint
        def heads_part(w_qb, w_kvb, w_o):
            q = qn(jnp.einsum("btr,rhd->bthd", cq, qn(w_qb)))
            kv_b = qn(jnp.einsum("btr,rhd->bthd", ckv, qn(w_kvb)))
            q_rope = qn(rotary_interleaved(q[..., hd:], theta))
            a = qn(causal_attention(q[..., :hd], kv_b[..., :hd], q_rope, k_rope,
                                    kv_b[..., hd:], qn))
            return a.reshape(b, t, -1) @ qn(w_o)

        a, _ = jax.lax.scan(
            lambda acc, ws: (acc + heads_part(*ws), None), jnp.zeros_like(x),
            (grouped(p[f"{name}/q_b/kernel"], hd + dr),
             grouped(p[f"{name}/kv_b/kernel"], hd + dv),
             p[f"{name}/o/kernel"].reshape(n, per * dv, c)))
        x = qn(x + qn(a))
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
        if arch["shared_experts"]:
            y = y + gated_mlp(u, p[f"{name}/shared_gate/kernel"],
                              p[f"{name}/shared_up/kernel"],
                              p[f"{name}/shared_down/kernel"], qn)
        return qn(x + y.reshape(b, t, c))

    return layer


def by_rows(layer, p, x, name, routed):
    """`layer` on x (B, T, C), one row of the batch at a time, each row
    rematerialized in the backward pass: the float32 activations of ONE row
    of one layer are all that ever stands."""
    # the loop's body is handed the layer's own leaves alone
    own = {k: v for k, v in p.items() if k.startswith(name + "/")}
    one = jax.checkpoint(lambda pp, row: layer(pp, row[None], name, routed)[0])
    return jax.lax.map(lambda row: one(own, row), x)


def last_hidden_for(arch, qn):
    """-> `f(params, tokens)`: the last layer's output, BEFORE the final norm."""
    layer = layer_for(arch, qn)

    def last_hidden(p, tokens):
        x = qn(p["embed/embedding"][tokens])
        for name, routed in layer_names(arch)[:arch["num_layers"]]:
            x = by_rows(layer, p, x, name, routed)
        return x

    return last_hidden


def logits_for(arch):
    """-> `f(params, tokens)`: the main path's float32 logits (B, T, V),
    whole — for small sizes (the test against published modelling code)."""
    last_hidden = last_hidden_for(arch, quantizer("float32"))

    def logits(p, tokens):
        with jax.default_matmul_precision("highest"):
            x = rms_norm(last_hidden(p, tokens), p["norm_final/scale"], arch["rms_eps"])
            return x @ p["lm_head/kernel"]

    return logits


def loss_parts_for(arch, precision: str = "float32"):
    """-> `f(params, tokens (B, T) i32, targets (B, T) i32)` -> (loss_main,
    loss_mtp). Below float32, `qn` rounds what a computation in that
    precision would hold in it: every matmul's operands and every layer's
    output; the norms' arithmetic, the router (its scores, choice and gates),
    the softmaxes and the loss stay float32."""
    qn = quantizer(precision)
    eps = arch["rms_eps"]
    layer, last_hidden = layer_for(arch, qn), last_hidden_for(arch, qn)

    def head_loss(p, x, targets, weights):
        """Sum over the rows of weight x CE(Head(x), target), in row blocks."""
        n = targets.size
        block = min(HEAD_BLOCK, n)
        assert n % block == 0, (n, block)
        head = qn(p["lm_head/kernel"])

        @jax.checkpoint
        def rows(total, xs):
            xb, tb, wb = xs
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
            ce = -jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]
            return total + jnp.sum(wb * ce), None

        total, _ = jax.lax.scan(
            rows, jnp.zeros((), jnp.float32),
            (x.reshape(n // block, block, -1), targets.reshape(n // block, block),
             weights.reshape(n // block, block)))
        return total

    def parts(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            x = last_hidden(p, tokens)
            main = head_loss(p, qn(rms_norm(x, p["norm_final/scale"], eps)), targets,
                             jnp.ones(targets.shape, jnp.float32)) / targets.size
            if not arch["mtp_layers"]:
                return main, jnp.zeros((), jnp.float32)
            emb = qn(p["embed/embedding"][targets])
            joined = jnp.concatenate(
                [rms_norm(x, p["mtp/norm_h/scale"], eps),
                 rms_norm(emb, p["mtp/norm_e/scale"], eps)], axis=-1)
            y = qn(qn(joined) @ qn(p["mtp/proj/kernel"]))
            y = by_rows(layer, p, y, "mtp/layer", True)
            y = qn(rms_norm(y, p["mtp/norm_final/scale"], eps))
            b, t = targets.shape
            live = jnp.ones((b, t), jnp.float32).at[:, -1].set(0.0)
            mtp = head_loss(p, y, jnp.roll(targets, -1, axis=1), live) / (b * (t - 1))
            return main, mtp

    return parts


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, tokens, targets)`: loss_main + mtp_weight x loss_mtp."""
    parts = loss_parts_for(arch, precision)

    def loss(p, tokens, targets):
        main, mtp = parts(p, tokens, targets)
        return main + arch["mtp_weight"] * mtp

    return loss
