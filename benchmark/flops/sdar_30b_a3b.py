"""Multiply-accumulates of SDAR-30B-A3B-Chat's block-diffusion step from
shapes alone, per ROW of `seq_len` tokens (the benchmark's "image" is one row
of the batch). A row goes through every layer as TWO streams, 2 x seq_len
positions: q/k/v/o projections and the router over all of them; attention
scores and weighted sums over the LIVE pairs of the two-stream mask only (L^2
+ L B of the (2L)^2: two triangles of blocks and a diagonal of B x B squares);
the experts HELD HERE at the expected top_k * held / num_experts slots a
position (uniform routing); the sliced head over the noised stream's L rows.
Norms, rotary, softmax, SiLU and the embedding lookup are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`gmm_flops` for the grouped expert matmuls from the step's COUNTED slots,
`attention_flops` for the flash kernels over the live pairs. Both count what
the mathematics needs (forward x 3), not what a kernel recomputes (the
backward kernels rebuild the score tile, `--remat` runs the forward twice),
and not what a kernel computes and masks away inside a tile that straddles
the mask's edge.
"""

from __future__ import annotations

from benchmark.flops.smallthinker import gmm_bytes, gmm_flops  # noqa: F401 — readers


def live_pairs(arch) -> int:
    """(query, key) pairs the two-stream mask allows in one row: clean ->
    clean L (L + B) / 2, noised -> clean L (L - B) / 2, noised -> noised L B."""
    length, block = arch["seq_len"], arch["diffusion_block"]
    return length * length + length * block


def score_macs(arch) -> int:
    """q k^T and p v over every layer's live pairs, all query heads, one row."""
    return 2 * arch["num_layers"] * arch["num_heads"] * arch["head_dim"] * live_pairs(arch)


def position_macs(arch) -> float:
    """Per position of a stream and layer: projections, router, held experts."""
    c, hd = arch["hidden_size"], arch["head_dim"]
    proj = c * hd * (2 * arch["num_heads"] + 2 * arch["num_kv_heads"])
    slots = arch["top_k"] * arch["experts_held"] / arch["num_experts"]
    return proj + c * arch["num_experts"] + slots * 3 * c * arch["expert_width"]


def forward_macs(arch, image_size: int = 0) -> float:
    """One row: 2 x seq_len positions through the layers, seq_len through the
    head (`image_size` is the image cells' key)."""
    length = arch["seq_len"]
    return (2 * length * arch["num_layers"] * position_macs(arch) + score_macs(arch)
            + length * arch["hidden_size"] * arch["vocab_size"])


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores and weighted sums over the live pairs, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)
