"""Multiply-accumulates of one ViT forward pass per image, from shapes
alone: patch embedding, per block the qkv / scores / weighted sum /
projection / MLP matmuls, and the classifier. LayerNorm, softmax and GELU
are not counted. Published: ViT-B/16 at 224 px = 17.5 GMAC (with a class
token, 197 tokens; the program pools 196 patch tokens, 0.5 % fewer)."""

from __future__ import annotations


def forward_macs(arch, image_size: int) -> int:
    d, pt = arch["dim"], arch["patch"]
    t = (image_size // pt) ** 2
    macs = t * pt * pt * 3 * d
    per_block = (t * d * 3 * d        # qkv
                 + 2 * t * t * d      # q.k^T and attn.v over all heads
                 + t * d * d          # projection
                 + 2 * t * d * 4 * d)  # MLP in and out
    return macs + arch["depth"] * per_block + d * arch["num_classes"]


def train_flops_per_image(arch, image_size: int) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)
