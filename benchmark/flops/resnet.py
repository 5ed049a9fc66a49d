"""Multiply-accumulates of one ResNet forward pass per image, from shapes
alone: every convolution (output positions x k x k x Cin x Cout) and the
classifier. Batch norm, ReLU and pooling are not counted (they are not
matrix work). Published: ResNet-50 at 224 px = 4.09 GMAC."""

from __future__ import annotations


def forward_macs(arch, image_size: int) -> int:
    bottleneck = arch["block"] == "bottleneck"
    exp = 4 if bottleneck else 1
    nf = arch["num_filters"]
    macs = 0
    if arch["stem"] == "imagenet":
        hw = (image_size + 2 * 3 - 7) // 2 + 1
        macs += hw * hw * 7 * 7 * 3 * nf
        hw = (hw + 2 - 3) // 2 + 1  # 3x3/2 max-pool, pad 1
    else:
        hw = image_size
        macs += hw * hw * 3 * 3 * 3 * nf
    cin = nf
    for i, n in enumerate(arch["stage_sizes"]):
        f = nf * 2 ** i
        for j in range(n):
            stride = 2 if (i > 0 and j == 0) else 1
            out = (hw + 2 - 3) // stride + 1 if stride == 2 else hw
            if bottleneck:
                macs += hw * hw * cin * f            # 1x1 at the input size
                macs += out * out * 9 * f * f        # 3x3 carries the stride
                macs += out * out * f * 4 * f        # 1x1 expand
            else:
                macs += out * out * 9 * cin * f
                macs += out * out * 9 * f * f
            if stride != 1 or cin != f * exp:
                macs += out * out * cin * f * exp    # 1x1 downsample
            cin, hw = f * exp, out
    return macs + cin * arch["num_classes"]


def train_flops_per_image(arch, image_size: int) -> float:
    """Forward x 3 (the backward pass is two matmuls for each forward one),
    2 FLOP per multiply-accumulate; nothing recomputed is counted."""
    return 2.0 * 3.0 * forward_macs(arch, image_size)
