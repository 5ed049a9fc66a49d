"""Multiply-accumulates of the SmallThinker decoder from shapes alone, per
ROW of `seq_len` tokens (the benchmark's "image" is one row of the batch):
q/k/v/o projections, the router, attention scores and weighted sums over the
exact band of each layer's mask, the experts HELD HERE at the expected
top_k * held / num_experts slots a token (uniform routing), and the sliced
head. Norms, rotary, softmax, ReLU and the embedding lookup are not counted.

Also the counts the kernel metrics divide by (benchmark/layers/):
`gmm_flops` for the grouped expert matmuls from the step's COUNTED slots,
`attention_flops` for the flash kernels over the unmasked band only.
Both count what the mathematics needs (forward x 3), not what a kernel
recomputes (the backward kernels rebuild the score tile, `--remat` runs the
forward twice): a share of the roofline that counted those would flatter a
kernel for doing the same work twice.
"""

from __future__ import annotations


def layout(arch, key: str):
    which = arch[key]
    return [int(which[i % len(which)]) for i in range(arch["num_layers"])]


def band_area(t: int, window: int) -> int:
    """Pairs (i, j) with j <= i and, with a window, j > i - window."""
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def score_macs(arch) -> int:
    """q k^T and p v over every layer's band, all query heads, one row."""
    t = arch["seq_len"]
    areas = sum(band_area(t, arch["window"] if w else 0)
                for w in layout(arch, "window_layout"))
    return 2 * arch["num_heads"] * arch["head_dim"] * areas


def token_macs(arch, with_head: bool = True) -> float:
    """Per token, everything but the score terms."""
    c, hd = arch["hidden_size"], arch["head_dim"]
    proj = c * hd * (2 * arch["num_heads"] + 2 * arch["num_kv_heads"])
    slots = arch["top_k"] * arch["experts_held"] / arch["num_experts"]
    expert = 3 * c * arch["expert_width"]
    per_layer = proj + c * arch["num_experts"] + slots * expert
    return arch["num_layers"] * per_layer + (c * arch["vocab_size"] if with_head else 0)


def forward_macs(arch, image_size: int = 0) -> float:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return arch["seq_len"] * token_macs(arch) + score_macs(arch)


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def gmm_flops(slots: float, arch) -> float:
    """The three grouped matmuls (gate, up, down) over `slots` token-slots
    of held experts, forward and backward (x 3)."""
    return 2.0 * 3.0 * slots * 3 * arch["hidden_size"] * arch["expert_width"]


def gmm_bytes(slots: float, arch) -> float:
    """bf16 traffic the grouped matmuls cannot avoid, forward and backward:
    rows in and out of each matmul, the held banks read twice and their
    gradient written once."""
    c, w = arch["hidden_size"], arch["expert_width"]
    rows = slots * (2 * c + 3 * w) * 2          # x, y and the three hiddens
    banks = arch["experts_held"] * 3 * c * w * 2
    return 3.0 * (rows + banks)


def attention_flops(arch, rows: int) -> float:
    """Scores and weighted sums over the unmasked band, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)
