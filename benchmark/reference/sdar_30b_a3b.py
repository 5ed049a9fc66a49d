"""Plain SDAR-30B-A3B-Chat (JetLM, `sdar_moe`, arXiv:2510.06303; config.json
keys in brackets) trained by diffusion over blocks (BD3-LM,
arXiv:2503.09573): the two-stream forward pass, its loss and, through
`jax.grad`, its gradients. Straightforward `jax.numpy`, float32, no flax, no
kernel, no sorting or grouping of tokens. Imports nothing from the program
under test.

`arch`: {"vocab_size", "hidden_size", "num_layers", "num_heads",
"num_kv_heads", "head_dim", "expert_width", "num_experts", "experts_held",
"first_expert", "top_k", "rope_theta", "rms_eps", "seq_len",
"diffusion_block", "diffusion_eps", "mask_id"}.

The batch is what the program's loader makes (its data/diffusion.py):
x_0 (B, L) the clean rows, and [x_t ; j] (B, 2, L): the noised rows and each
position's level as an integer. A row is cut into blocks of B =
[diffusion_block] tokens; block b drew j_b uniform on 1..65,536, t_b = eps +
(1 - eps) j_b / 65,536, and each of its tokens was replaced by the mask id
with probability t_b. With blk(i) = i // B the model has to give, for every
masked position i, p(x_0[i] | x_t[block blk(i)], x_0[blocks < blk(i)]).

All blocks of a row in ONE pass over two streams, [x_0 ; x_t]: 2L positions
through every layer with the same weights, position ids 0..L-1 in BOTH
streams. One layer, x (2L, C), every projection without bias:

    h  = RMSNorm(x; g_in)                                  [rms_norm_eps]
    q, k, v = h W_q, h W_k, h W_v      H = 32 query heads on H_kv = 4 KV heads
                                       of [head_dim] 128
    q, k = RMSNorm over each head's 128 dims (one scale for all heads:
           q_head_norm, k_head_norm), then the rotary embedding (rotate-half
           over the whole head, theta [rope_theta] 1e6) at the position's id
    a  = softmax(q k^T / sqrt(128) + mask) v, query row i -> key col j:
           clean  i -> clean  j   iff blk(i) >= blk(j)
           noised i -> clean  j   iff blk(i) >  blk(j)
           noised i -> noised j   iff blk(i) =  blk(j)
           clean  i -> noised j   never
    x1 = x + a W_o
    u  = RMSNorm(x1; g_post)
    r  = u W_r                    router logits over ALL [num_experts] 128
    S  = the [num_experts_per_tok] 8 largest of r;  w = softmax(r[S])
         ([norm_topk_prob]: the softmax over all, renormalised over S)
    y  = sum over e in S of w_e W_down^e (silu(W_gate^e u) * (W_up^e u))
         SwiGLU experts [moe_intermediate_size] 768 wide; no shared expert
    x2 = x1 + y

then the NOISED stream's L states through a final RMSNorm and an untied head,
and

    loss = (1 / (rows L)) sum over rows, blocks b, positions i in b with
           x_t[i] = mask of  -log softmax(h_i W_head)[x_0[i]] / t_b

at the position itself (no shift). The clean stream gives keys and values
and no loss.

The chip's share (model-configs guide, section 4): only experts
`first_expert .. first_expert + experts_held - 1` exist here. The router
keeps its full width and its top_k; a chosen expert that is not held adds
nothing, and that partial result is what goes on. `vocab_size` is the slice
of the vocabulary held here, and `mask_id` an id of the slice.

Departures / assumptions, the program's too (its models/decoder_lm.py):
- block length, the noise (linear schedule, one level a block, the 16-bit
  grid, eps, the weight 1 / t, the normalisation by all L positions, no
  shift) and the mask id's place in the slice are not in config.json: the
  configuration's `assumed` lists each with its source;
- the router reads u, the post-attention norm (the family's published
  modelling code routes inside the MLP block, which the post-attention norm
  feeds); no auxiliary balance loss;
- no attention bias [attention_bias false], no sliding window
  [use_sliding_window false];
- packed rows attend across document boundaries.

How it fits: 646 M float32 parameters with their gradient and Adam's two
moments are 10.3 GB of a chip's 16 before any activation, and one head's
16,384^2 float32 scores would be 1.07 GB. So every layer is a
`jax.checkpoint`, attention walks the QUERIES in blocks of 128 (one block's
(H, 128, 2L) scores at a time, 268 MB at the cell's sizes, rematerialized;
the mask built from iotas per block), every HELD expert is applied to every
position under its 0/1-masked gate inside a rematerialized scan over the
experts, and the head takes the rows in blocks. None of that changes a value.
The step that follows it (parameters, gradient, Adam's two moments and 4.39 GB
of temporaries: 14.7 GB by the compiler's count for a described v5e) fits the
chip once the program's own state is freed.

Leaf names are the program's key paths joined by "/". Initial weights:
1/sqrt(fan-in) normal kernels and expert banks, N(0, 0.02) embedding, norm
scales 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec
from .smallthinker import quantizer, rms_norm

QUERY_BLOCK = 128   # queries per attention block
HEAD_BLOCK = 1024   # rows per block of the head and its loss
LEVELS = 65536      # the loader's grid of noise levels


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
        normal(f"{b}/q/kernel", (c, arch["num_heads"] * hd), c)
        normal(f"{b}/k/kernel", (c, arch["num_kv_heads"] * hd), c)
        normal(f"{b}/v/kernel", (c, arch["num_kv_heads"] * hd), c)
        spec[f"{b}/q_head_norm/scale"] = ((hd,), "ones", 0.0)
        spec[f"{b}/k_head_norm/scale"] = ((hd,), "ones", 0.0)
        normal(f"{b}/o/kernel", (arch["num_heads"] * hd, c), arch["num_heads"] * hd)
        spec[f"{b}/norm_post/scale"] = ((c,), "ones", 0.0)
        normal(f"{b}/router", (c, arch["num_experts"]), c)
        normal(f"{b}/w_gate", (held, c, width), c)
        normal(f"{b}/w_up", (held, c, width), c)
        normal(f"{b}/w_down", (held, width, c), width)
    spec["norm_final/scale"] = ((c,), "ones", 0.0)
    normal("lm_head/kernel", (c, arch["vocab_size"]), c)
    return spec


def rotary(x, theta, positions):
    """x (B, T, H, D) at `positions` (T,): dimension i paired with i + D/2."""
    d = x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def seen(rows, cols, length: int, block: int):
    """The four rules: whether query position `rows` sees key position `cols`
    of a [clean ; noised] row of 2 x `length` positions in blocks of `block`."""
    q_noised, k_noised = rows >= length, cols >= length
    q_blk, k_blk = (rows % length) // block, (cols % length) // block
    return ((~q_noised & ~k_noised & (q_blk >= k_blk))
            | (q_noised & ~k_noised & (q_blk > k_blk))
            | (q_noised & k_noised & (q_blk == k_blk)))


def two_stream_attention(q, k, v, length: int, block: int, qn, rule=seen):
    """q (B, 2L, H, D), k/v (B, 2L, H_kv, D) -> (B, 2L, H, D) under the mask
    of `rule`, built from iotas; query head h reads KV head h // (H / H_kv).
    Queries in blocks."""
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    step = min(QUERY_BLOCK, t)
    assert t % step == 0, (t, step)
    qs = q.reshape(b, t // step, step, h_kv, h // h_kv, d).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def one(start, qb):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qn(qb), qn(k)) / math.sqrt(d)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (step, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (step, t), 1)
        p = jax.nn.softmax(jnp.where(rule(rows, cols, length, block), s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", qn(p), qn(v))

    out = jax.lax.map(lambda xs: one(*xs), (jnp.arange(t // step) * step, qs))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, d)


def held_experts(u, logits, w_gate, w_up, w_down, arch, qn):
    """u (N, C), router logits (N, E) -> the held experts' part of the
    mixture (N, C): each held expert on every position, times the position's
    gate for it (0 where it did not choose it)."""
    vals, idx = jax.lax.top_k(logits, arch["top_k"])
    weight = jax.nn.softmax(vals, axis=-1)
    first = arch["first_expert"]

    @jax.checkpoint
    def one(y, xs):
        e, wg, wu, wd = xs
        gate = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        hid = qn(jax.nn.silu(qn(qn(u) @ qn(wg))) * qn(qn(u) @ qn(wu)))
        return y + gate[:, None] * qn(hid @ qn(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def states_for(arch, precision: str = "float32", rule=seen, positions=None):
    """-> `states(params, tokens (B, T))`: the last layer's output before
    the final norm, the row run as two streams of T / 2 under `rule` at
    `positions` (default: 0..T/2-1 twice). What `loss_for` and the tests of
    the per-block definition share."""
    qn = quantizer(precision)
    hd, eps = arch["head_dim"], arch["rms_eps"]

    def layer(p, x, name, at):
        b, t, c = x.shape
        h = qn(rms_norm(x, p[f"{name}/norm_in/scale"], eps))
        q = qn(h @ qn(p[f"{name}/q/kernel"])).reshape(b, t, arch["num_heads"], hd)
        k = qn(h @ qn(p[f"{name}/k/kernel"])).reshape(b, t, arch["num_kv_heads"], hd)
        v = qn(h @ qn(p[f"{name}/v/kernel"])).reshape(b, t, arch["num_kv_heads"], hd)
        q = qn(rotary(qn(rms_norm(q, p[f"{name}/q_head_norm/scale"], eps)),
                      arch["rope_theta"], at))
        k = qn(rotary(qn(rms_norm(k, p[f"{name}/k_head_norm/scale"], eps)),
                      arch["rope_theta"], at))
        a = qn(two_stream_attention(q, k, v, t // 2, arch["diffusion_block"], qn,
                                    rule))
        x = qn(x + qn(a.reshape(b, t, -1) @ qn(p[f"{name}/o/kernel"])))
        u32 = rms_norm(x, p[f"{name}/norm_post/scale"], eps)
        logits = u32 @ p[f"{name}/router"]
        y = held_experts(qn(u32).reshape(b * t, c), logits.reshape(b * t, -1),
                         p[f"{name}/w_gate"], p[f"{name}/w_up"],
                         p[f"{name}/w_down"], arch, qn)
        return qn(x + y.reshape(b, t, c))

    def states(p, tokens):
        t = tokens.shape[1]
        at = jnp.tile(jnp.arange(t // 2), 2) if positions is None else positions
        x = qn(p["embed/embedding"][tokens])
        for i in range(arch["num_layers"]):
            x = jax.checkpoint(
                lambda pp, xx, i=i: layer(pp, xx, f"layer{i}", at))(p, x)
        return x

    return states


def head_logits(arch, p, x, qn=lambda x: x):
    """States (.., C) before the final norm -> logits (.., V)."""
    return qn(rms_norm(x, p["norm_final/scale"], arch["rms_eps"])) @ qn(p["lm_head/kernel"])


def loss_for(arch, precision: str = "float32"):
    """-> `loss(params, x_0 (B, L) i32, [x_t ; j] (B, 2, L) i32)`: block
    diffusion's weighted cross-entropy as the docstring writes it. Below
    float32, `qn` rounds what a computation in that precision would hold in
    it: every matmul's operands and every layer's output; the norms'
    arithmetic, the router (its logits, top-k and gates), the softmaxes and
    the loss stay float32."""
    qn = quantizer(precision)
    states = states_for(arch, precision)
    eps = arch["diffusion_eps"]

    def loss(p, clean, noised_and_level):
        with jax.default_matmul_precision("highest"):
            noised, level = noised_and_level[:, 0], noised_and_level[:, 1]
            length = clean.shape[1]
            x = states(p, jnp.concatenate([clean, noised], axis=1))[:, length:]
            x = qn(rms_norm(x, p["norm_final/scale"], arch["rms_eps"]))
            t = eps + (1.0 - eps) * level.astype(jnp.float32) / LEVELS
            weight = (noised == arch["mask_id"]) / t
            n = clean.size
            block = min(HEAD_BLOCK, n)
            assert n % block == 0, (n, block)
            head = qn(p["lm_head/kernel"])

            @jax.checkpoint
            def rows(total, xs):
                xb, tb, wb = xs
                logp = jax.nn.log_softmax(xb @ head, axis=-1)
                at = jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]
                return total - jnp.sum(wb * at), None

            total, _ = jax.lax.scan(
                rows, jnp.zeros((), jnp.float32),
                (x.reshape(n // block, block, -1), clean.reshape(n // block, block),
                 weight.reshape(n // block, block)))
            return total / n

    return loss
