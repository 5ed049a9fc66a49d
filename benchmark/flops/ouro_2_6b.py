"""Multiply-accumulates of the Ouro-2.6B looped decoder from shapes alone, per
ROW of `seq_len` tokens (the benchmark's "image" is one row of the batch):
the q/k/v/o projections and the gated MLP of each of the `loops` x
`num_layers` layer APPLICATIONS (every pass is work the model needs, unlike
recomputation), scores and weighted sums over head_dim + head_dim on the
exact causal triangle in each, and the untied head once a pass. Norms,
rotary, softmax, SiLU, the exit gate and the embedding lookup are not
counted.

Also what the kernel metric divides by (benchmark/layers/attn_roofline_pct):
`attention_flops`, over all the layer applications. All count what the
mathematics needs (forward x 3), not what a kernel recomputes.
"""

from __future__ import annotations


def applications(arch) -> int:
    """Layers a step runs: the same `num_layers` leaves, `loops` times."""
    return arch["loops"] * arch["num_layers"]


def triangle(t: int) -> int:
    """Pairs (i, j) with j <= i."""
    return t * (t + 1) // 2


def score_macs(arch) -> int:
    """q k^T and p v, head_dim each, on the causal triangle, all query
    heads, every layer application, one row."""
    return (applications(arch) * arch["num_heads"] * 2 * arch["head_dim"]
            * triangle(arch["seq_len"]))


def layer_token_macs(arch) -> int:
    """One layer application, a token: q, k, v, o and the gated MLP."""
    c, hd = arch["hidden_size"], arch["head_dim"]
    return (c * hd * (2 * arch["num_heads"] + 2 * arch["num_kv_heads"])
            + 3 * c * arch["dense_width"])


def head_token_macs(arch) -> int:
    """The head, a token: once a pass."""
    return arch["loops"] * arch["hidden_size"] * arch["vocab_size"]


def forward_macs(arch, image_size: int = 0) -> int:
    """One row of `seq_len` tokens (`image_size` is the image cells' key)."""
    return (arch["seq_len"] * (applications(arch) * layer_token_macs(arch)
                               + head_token_macs(arch)) + score_macs(arch))


def train_flops_per_image(arch, image_size: int = 0) -> float:
    """Forward x 3, 2 FLOP per multiply-accumulate; nothing recomputed."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)


def attention_flops(arch, rows: int) -> float:
    """Scores and weighted sums (128 + 128 a pair) on the causal triangle,
    every layer application, forward x 3."""
    return 2.0 * 3.0 * rows * score_macs(arch)


def loop_flops(arch, rows: int) -> float:
    """Everything under the program's `loop` scope: the layer applications'
    matmuls and scores, forward x 3 (beside `loop_device_ms`)."""
    return 2.0 * 3.0 * rows * (arch["seq_len"] * applications(arch)
                               * layer_token_macs(arch) + score_macs(arch))


def exit_head_flops(arch, rows: int) -> float:
    """The head's matmul over the `loops` sets of states, forward x 3
    (beside `exit_head_device_ms`; the gate's dot products are not in it)."""
    return 2.0 * 3.0 * rows * arch["seq_len"] * head_token_macs(arch)
