"""Multiply-accumulates of the LFM2-8B-A1B decoder from shapes alone, per ROW
of `seq_len` tokens (the benchmark's "image" is one row of the batch): the
short convolution's two matmuls (W_in of 3C columns, W_out) and its taps, the
attention layers' q/k/v/o projections with scores and weighted sums over
head_dim + head_dim on the exact causal triangle, the leading dense layers'
gated MLP, the router, the experts HELD HERE at the expected top_k * held /
num_experts slots a token (uniform routing), and the sliced, tied head.
Norms, rotary, softmax, SiLU, the gates' products and the embedding lookup
are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`attention_flops` for the flash kernels (the attention layers only),
`gmm_flops` for the grouped expert matmuls from the step's COUNTED slots, and
`conv_flops` beside `conv_device_ms`. All count what the mathematics needs
(forward x 3), not what a kernel recomputes.
"""

from __future__ import annotations


def conv_layout(arch) -> list:
    which = arch["conv_layout"]
    return [int(which[i % len(which)]) for i in range(arch["num_layers"])]


def attention_blocks(arch) -> int:
    return conv_layout(arch).count(0)


def triangle(t: int) -> int:
    """Pairs (i, j) with j <= i."""
    return t * (t + 1) // 2


def score_macs(arch) -> int:
    """q k^T and p v, head_dim each, on the causal triangle, all query
    heads, every attention layer, one row."""
    return (attention_blocks(arch) * arch["num_heads"] * 2 * arch["head_dim"]
            * triangle(arch["seq_len"]))


def conv_token_macs(arch) -> int:
    """One short-convolution operator, a token: W_in and W_out."""
    c = arch["hidden_size"]
    return c * 3 * c + c * c


def conv_tap_macs(arch) -> int:
    """The depthwise taps of one operator, a token (L a channel): stated
    apart, they run on the VPU and not on the MXU."""
    return arch["conv_kernel"] * arch["hidden_size"]


def attention_token_macs(arch) -> int:
    c, hd = arch["hidden_size"], arch["head_dim"]
    return c * hd * (2 * arch["num_heads"] + 2 * arch["num_kv_heads"])


def expert_macs(arch) -> int:
    """One expert (gate, up, down) on one token."""
    return 3 * arch["hidden_size"] * arch["expert_width"]


def routed_token_macs(arch) -> float:
    """A routing layer's feed-forward, a token: router and the expected
    slots on held experts."""
    slots = arch["top_k"] * arch["experts_held"] / arch["num_experts"]
    return arch["hidden_size"] * arch["num_experts"] + slots * expert_macs(arch)


def layers_token_macs(arch) -> float:
    """Per token, the layers outside the score terms — with all experts
    held, the "active parameters" of the layers."""
    convs = conv_layout(arch).count(1)
    dense = 3 * arch["hidden_size"] * arch["dense_width"]
    return (convs * (conv_token_macs(arch) + conv_tap_macs(arch))
            + attention_blocks(arch) * attention_token_macs(arch)
            + arch["dense_layers"] * dense
            + (arch["num_layers"] - arch["dense_layers"]) * routed_token_macs(arch))


def token_macs(arch, with_head: bool = True) -> float:
    """Per token, everything but the score terms."""
    head = arch["hidden_size"] * arch["vocab_size"] if with_head else 0
    return layers_token_macs(arch) + head


def forward_macs(arch, image_size: int = 0) -> float:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return arch["seq_len"] * token_macs(arch) + score_macs(arch)


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores and weighted sums (64 + 64 a pair) on the causal triangle,
    the attention layers only, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)


def gmm_flops(slots: float, arch) -> float:
    """The three grouped matmuls (gate, up, down) over `slots` token-slots
    of held experts, forward and backward (x 3)."""
    return 2.0 * 3.0 * slots * expert_macs(arch)


def conv_flops(arch, tokens: int) -> float:
    """W_in and W_out of every short-convolution layer on `tokens` tokens,
    forward x 3; the taps (`conv_tap_macs`: 2 x L x C FLOP a token and layer)
    are not in it."""
    return 2.0 * 3.0 * tokens * conv_layout(arch).count(1) * conv_token_macs(arch)
