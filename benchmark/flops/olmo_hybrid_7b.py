"""Multiply-accumulates of the Olmo-Hybrid-7B decoder from shapes alone, per
ROW of `seq_len` tokens (the benchmark's "image" is one row of the batch), of
the heads HELD HERE (`heads_held` of `num_heads`): the Gated DeltaNet layers'
five projections (q, k of heads x gdn_key_dim; v, the gate, W_o of heads x
gdn_value_dim), their two head-wide projections (the decay's, beta's), the
taps, and the recurrence at a NOMINAL chunk of 64 tokens whatever the program
computes in; the attention layer's four projections, scores and weighted
sums over head_dim + head_dim on the exact causal triangle; the SwiGLU, which
stands WHOLE beside half the heads; the sliced head. Norms, softmax, SiLU,
the decays' exponentials and the embedding lookup are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`attention_flops` for the flash kernels (the ONE attention layer) and
`gdn_core_bound_s`, the least time the chip could take for the recurrence,
for `gdn_core_roofline_pct`. All count what the mathematics needs (forward
x 3), not what an implementation recomputes.
"""

from __future__ import annotations

GDN_CHUNK = 64      # the nominal chunk the recurrence is counted at


def gdn_layers(arch) -> int:
    which = arch["gdn_layout"]
    return sum(int(which[i % len(which)]) for i in range(arch["num_layers"]))


def attention_blocks(arch) -> int:
    return arch["num_layers"] - gdn_layers(arch)


def held(arch):
    """(query heads, KV heads) computed here."""
    heads = arch["heads_held"] or arch["num_heads"]
    return heads, arch["num_kv_heads"] * heads // arch["num_heads"]


def triangle(t: int) -> int:
    """Pairs (i, j) with j <= i."""
    return t * (t + 1) // 2


def score_macs(arch) -> int:
    """q k^T and p v, head_dim each, on the causal triangle, the held query
    heads, the attention layers, one row."""
    return (attention_blocks(arch) * held(arch)[0] * 2 * arch["head_dim"]
            * triangle(arch["seq_len"]))


def attention_token_macs(arch) -> int:
    """One attention block, a token: W_q, W_k, W_v, W_o of the held heads."""
    heads, kv_heads = held(arch)
    return arch["hidden_size"] * arch["head_dim"] * (2 * heads + 2 * kv_heads)


def gdn_token_macs(arch) -> int:
    """One Gated DeltaNet block outside its recurrence, a token: W_q, W_k
    (d_k a head), W_v, W_g, W_o (d_v a head), the two head-wide projections,
    and the taps of the three depthwise convolutions (the VPU's, stated with
    the rest)."""
    c, heads = arch["hidden_size"], held(arch)[0]
    dk, dv = arch["gdn_key_dim"], arch["gdn_value_dim"]
    return (c * heads * (2 * dk + 3 * dv) + 2 * c * heads
            + arch["conv_kernel"] * heads * (2 * dk + dv))


def gdn_core_macs(arch) -> int:
    """The recurrence of ONE Gated DeltaNet layer over one row, in the chunked
    form at a chunk of C = GDN_CHUNK tokens, per head and chunk: the two
    triangles of scores A (k k^T) and P (q k^T), C^2 / 2 x d_k each; the
    forward substitution of (I + A) into [beta e^G K | beta V], C^2 / 2 x
    (d_k + d_v); W S_0, (e^G Q) S_0 and K^T U against the state, C d_k d_v
    each; P U, C^2 / 2 x d_v."""
    c, dk, dv = GDN_CHUNK, arch["gdn_key_dim"], arch["gdn_value_dim"]
    per_chunk = c * c * dk + c * c * (dk + dv) // 2 + 3 * c * dk * dv + c * c * dv // 2
    return held(arch)[0] * (arch["seq_len"] // c) * per_chunk


def gdn_core_bytes(arch) -> int:
    """What one layer's recurrence has to move for one row, once: q, k, v in
    and o out in bf16, the log decay g and beta in float32, one each a head."""
    dk, dv = arch["gdn_key_dim"], arch["gdn_value_dim"]
    return arch["seq_len"] * held(arch)[0] * ((2 * dk + 2 * dv) * 2 + 4 + 4)


def gdn_core_bound_s(arch, rows: int, flops_per_s: float = 197e12,
                     bytes_per_s: float = 819e9) -> float:
    """The least seconds a step's recurrences could take on the chip (the
    defaults: a TPU v5e, benchmark/peaks.json): the larger of their FLOPs
    over the bf16 peak and their bytes over the HBM's bandwidth, forward x 3,
    every Gated DeltaNet layer, `rows` rows. At these shapes the bytes bind
    (about 130 FLOP a byte against the chip's 240)."""
    layers = gdn_layers(arch)
    flops = 2.0 * 3.0 * rows * layers * gdn_core_macs(arch)
    moved = 3.0 * rows * layers * gdn_core_bytes(arch)
    return max(flops / flops_per_s, moved / bytes_per_s)


def token_macs(arch, with_head: bool = True) -> int:
    """Per token, everything but the score terms and the recurrence."""
    head = arch["hidden_size"] * arch["vocab_size"] if with_head else 0
    return (gdn_layers(arch) * gdn_token_macs(arch)
            + attention_blocks(arch) * attention_token_macs(arch)
            + arch["num_layers"] * 3 * arch["hidden_size"] * arch["dense_width"]
            + head)


def forward_macs(arch, image_size: int = 0) -> int:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return (arch["seq_len"] * token_macs(arch) + score_macs(arch)
            + gdn_layers(arch) * gdn_core_macs(arch))


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores and weighted sums (128 + 128 a pair) on the causal triangle,
    the held heads of the attention layer, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)


def gdn_flops(arch, tokens: int) -> float:
    """Everything a step's Gated DeltaNet blocks compute on `tokens` tokens
    (whole rows): projections, taps and the recurrence, forward x 3 — the log
    line beside `gdn_device_ms`."""
    return 2.0 * 3.0 * gdn_layers(arch) * (
        tokens * gdn_token_macs(arch) + tokens / arch["seq_len"] * gdn_core_macs(arch))
