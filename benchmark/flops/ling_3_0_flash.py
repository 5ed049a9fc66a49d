"""Multiply-accumulates of the Ling-3.0-flash decoder from shapes alone, per
ROW of `seq_len` tokens (the benchmark's "image" is one row of the batch):
the KDA layers' five projections of hidden x (heads x head_dim) (q, k, v, the
decay's W_f, W_o), their two head-wide gates (beta, the output gate), the
taps, and the recurrence at a NOMINAL chunk of 64 tokens whatever chunk the
program computes in; the latent attention layer's four projections (no query
bottleneck) and its output gate, scores over head_dim + rope_dim and weighted
sums over v_head_dim on the exact causal triangle; the leading dense layers'
gated MLP; the router, the experts HELD HERE at the expected top_k * held /
num_experts slots a token (uniform routing), the shared expert; the sliced
head. Norms, rotary, softmax, SiLU, the decays' exponentials and the
embedding lookup are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`attention_flops` for the flash kernels (the ONE latent layer),
`gmm_flops` for the grouped expert matmuls from the step's COUNTED slots,
`shared_flops` beside `moe_shared_device_ms`, and `kda_core_bound_s`, the
least time the chip could take for the recurrence, for
`kda_core_roofline_pct`. All count what the mathematics needs (forward x 3),
not what an implementation recomputes.
"""

from __future__ import annotations

KDA_CHUNK = 64      # the nominal chunk the recurrence is counted at


def kda_layout(arch) -> list:
    which = arch["kda_layout"]
    return [int(which[i % len(which)]) for i in range(arch["num_layers"])]


def attention_blocks(arch) -> int:
    return kda_layout(arch).count(0)


def routing_layers(arch) -> int:
    return arch["num_layers"] - arch["dense_layers"]


def triangle(t: int) -> int:
    """Pairs (i, j) with j <= i."""
    return t * (t + 1) // 2


def score_macs(arch) -> int:
    """q k^T over head_dim + rope_dim and p v over v_head_dim on the causal
    triangle, all query heads, the latent attention layers, one row."""
    per_pair = arch["head_dim"] + arch["rope_dim"] + arch["v_head_dim"]
    return (attention_blocks(arch) * arch["num_heads"] * per_pair
            * triangle(arch["seq_len"]))


def attention_token_macs(arch) -> int:
    """One latent-attention block, a token: W_q (no bottleneck), W_kva,
    W_kvb, the head-wise gate, W_o."""
    c, heads = arch["hidden_size"], arch["num_heads"]
    hd, dr, dv = arch["head_dim"], arch["rope_dim"], arch["v_head_dim"]
    return (c * heads * (hd + dr) + c * (arch["kv_rank"] + dr)
            + arch["kv_rank"] * heads * (hd + dv)
            + arch["out_gate"] * c * heads + heads * dv * c)


def kda_token_macs(arch) -> int:
    """One KDA block outside its recurrence, a token: W_q, W_k, W_v, W_f,
    W_o, the two head-wide projections (beta, the gate), and the taps of the
    three depthwise convolutions (the VPU's, stated with the rest)."""
    c, wide = arch["hidden_size"], arch["num_heads"] * arch["head_dim"]
    return (5 * c * wide + 2 * c * arch["num_heads"]
            + 3 * arch["conv_kernel"] * wide)


def kda_core_macs(arch) -> float:
    """The recurrence of ONE KDA layer over one row, in the chunked form at
    a chunk of C = KDA_CHUNK tokens, per head and chunk: the two triangles
    of scores A (k k^T) and P (q k^T), C^2 / 2 x d_k each; the forward
    substitution of (I + A) into [beta K Gamma | beta V], C^2 / 2 x (d_k +
    d_v); W S_0, (Q Gamma) S_0 and K^T U against the state, C d_k d_v each;
    P U, C^2 / 2 x d_v."""
    c, d = KDA_CHUNK, arch["head_dim"]
    per_chunk = c * c * d + c * c * d + 3 * c * d * d + c * c * d // 2
    return arch["num_heads"] * (arch["seq_len"] // c) * per_chunk


def kda_core_bytes(arch) -> float:
    """What one KDA layer's recurrence has to move for one row, once: q, k,
    v in and o out in bf16, the log decay g and beta in float32."""
    d = arch["head_dim"]
    return arch["seq_len"] * arch["num_heads"] * (4 * d * 2 + d * 4 + 4)


def kda_core_bound_s(arch, rows: int, flops_per_s: float = 197e12,
                     bytes_per_s: float = 819e9) -> float:
    """The least seconds a step's recurrences could take on the chip (the
    defaults: a TPU v5e, benchmark/peaks.json): the larger of their FLOPs
    over the bf16 peak and their bytes over the HBM's bandwidth, forward x 3,
    every KDA layer, `rows` rows. At these shapes the bytes bind (about 7 FLOP
    a byte against the chip's 240)."""
    layers = kda_layout(arch).count(1)
    flops = 2.0 * 3.0 * rows * layers * kda_core_macs(arch)
    moved = 3.0 * rows * layers * kda_core_bytes(arch)
    return max(flops / flops_per_s, moved / bytes_per_s)


def expert_macs(arch) -> int:
    """One expert (gate, up, down) on one token."""
    return 3 * arch["hidden_size"] * arch["expert_width"]


def routed_token_macs(arch) -> float:
    """A routing layer's feed-forward, a token: router, the expected slots
    on held experts, the shared expert."""
    slots = arch["top_k"] * arch["experts_held"] / arch["num_experts"]
    return (arch["hidden_size"] * arch["num_experts"]
            + (slots + arch["shared_experts"]) * expert_macs(arch))


def layers_token_macs(arch) -> float:
    """Per token, the layers outside the score terms and the recurrence —
    with all experts held, the "active parameters" of the layers."""
    dense = 3 * arch["hidden_size"] * arch["dense_width"]
    return (kda_layout(arch).count(1) * kda_token_macs(arch)
            + attention_blocks(arch) * attention_token_macs(arch)
            + arch["dense_layers"] * dense
            + routing_layers(arch) * routed_token_macs(arch))


def token_macs(arch, with_head: bool = True) -> float:
    """Per token, everything but the score terms and the recurrence."""
    head = arch["hidden_size"] * arch["vocab_size"] if with_head else 0
    return layers_token_macs(arch) + head


def forward_macs(arch, image_size: int = 0) -> float:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return (arch["seq_len"] * token_macs(arch) + score_macs(arch)
            + kda_layout(arch).count(1) * kda_core_macs(arch))


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores (over 192) and weighted sums (over 128) on the causal
    triangle, the latent attention layer only, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)


def gmm_flops(slots: float, arch) -> float:
    """The three grouped matmuls (gate, up, down) over `slots` token-slots
    of held experts, forward and backward (x 3)."""
    return 2.0 * 3.0 * slots * expert_macs(arch)


def shared_flops(arch, tokens: int) -> float:
    """The shared expert on every token of every routing layer, x 3."""
    return (2.0 * 3.0 * tokens * routing_layers(arch)
            * arch["shared_experts"] * expert_macs(arch))


def kda_flops(arch, tokens: int) -> float:
    """Everything a step's KDA blocks compute on `tokens` tokens (whole rows):
    projections, gates, taps and the recurrence, forward x 3 — the log line
    beside `kda_device_ms`."""
    layers = kda_layout(arch).count(1)
    return 2.0 * 3.0 * layers * (tokens * kda_token_macs(arch)
                                 + tokens / arch["seq_len"] * kda_core_macs(arch))
