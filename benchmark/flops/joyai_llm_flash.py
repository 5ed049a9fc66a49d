"""Multiply-accumulates of the JoyAI-LLM-Flash decoder (the DeepSeek-V3 layer)
from shapes alone, per ROW of `seq_len` tokens (the benchmark's "image" is one
row of the batch): the latent attention's five projections, scores over
head_dim + rope_dim and weighted sums over v_head_dim on the exact causal
triangle, the leading dense layers' gated MLP, the router, the experts HELD
HERE at the expected top_k * held / num_experts slots a token (uniform
routing), the shared expert, the prediction module (its projection, its
layer, its use of the head) and the sliced head. Norms, rotary, softmax, SiLU
and the embedding lookups are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`attention_flops` for the flash kernels (all attention blocks, the
module's too), `gmm_flops` for the grouped expert matmuls from the step's
COUNTED slots, and `shared_flops` / `mtp_flops` beside the two device-time
metrics this configuration adds. All count what the mathematics needs
(forward x 3), not what a kernel recomputes.
"""

from __future__ import annotations


def attention_blocks(arch) -> int:
    return arch["num_layers"] + arch["mtp_layers"]


def routing_layers(arch) -> int:
    return arch["num_layers"] - arch["dense_layers"] + arch["mtp_layers"]


def triangle(t: int) -> int:
    """Pairs (i, j) with j <= i."""
    return t * (t + 1) // 2


def score_macs(arch, blocks: int | None = None) -> int:
    """q k^T over head_dim + rope_dim and p v over v_head_dim on the causal
    triangle, all query heads, `blocks` attention blocks, one row."""
    blocks = attention_blocks(arch) if blocks is None else blocks
    per_pair = arch["head_dim"] + arch["rope_dim"] + arch["v_head_dim"]
    return blocks * arch["num_heads"] * per_pair * triangle(arch["seq_len"])


def attention_token_macs(arch) -> int:
    """The five projections of one latent-attention block, a token."""
    c, heads = arch["hidden_size"], arch["num_heads"]
    hd, dr, dv = arch["head_dim"], arch["rope_dim"], arch["v_head_dim"]
    return (c * arch["q_rank"] + arch["q_rank"] * heads * (hd + dr)
            + c * (arch["kv_rank"] + dr) + arch["kv_rank"] * heads * (hd + dv)
            + heads * dv * c)


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
    """Per token, the `num_layers` layers outside the score terms — with all
    experts held, the "active parameters" of the layers."""
    dense = 3 * arch["hidden_size"] * arch["dense_width"]
    return (arch["num_layers"] * attention_token_macs(arch)
            + arch["dense_layers"] * dense
            + (arch["num_layers"] - arch["dense_layers"]) * routed_token_macs(arch))


def mtp_token_macs(arch) -> float:
    """Per token, the prediction module outside its score terms: W_eh, one
    routing layer, the head a second time."""
    c = arch["hidden_size"]
    return arch["mtp_layers"] * (2 * c * c + attention_token_macs(arch)
                                 + routed_token_macs(arch) + c * arch["vocab_size"])


def token_macs(arch, with_head: bool = True) -> float:
    """Per token, everything but the score terms."""
    head = arch["hidden_size"] * arch["vocab_size"] if with_head else 0
    return layers_token_macs(arch) + mtp_token_macs(arch) + head


def forward_macs(arch, image_size: int = 0) -> float:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return arch["seq_len"] * token_macs(arch) + score_macs(arch)


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores (over 192) and weighted sums (over 128) on the causal
    triangle, every attention block, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)


def gmm_flops(slots: float, arch) -> float:
    """The three grouped matmuls (gate, up, down) over `slots` token-slots
    of held experts, forward and backward (x 3)."""
    return 2.0 * 3.0 * slots * expert_macs(arch)


def shared_flops(arch, tokens: int) -> float:
    """The shared expert on every token of every routing layer, x 3."""
    return (2.0 * 3.0 * tokens * routing_layers(arch)
            * arch["shared_experts"] * expert_macs(arch))


def mtp_flops(arch, rows: int) -> float:
    """The whole prediction module for `rows` rows, x 3."""
    return 2.0 * 3.0 * rows * (arch["seq_len"] * mtp_token_macs(arch)
                               + score_macs(arch, arch["mtp_layers"]))
