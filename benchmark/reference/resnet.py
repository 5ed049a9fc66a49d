"""Plain ResNet v1 (He et al., arXiv:1512.03385, table 1; the torchvision
layout: stride on the 3x3 of a bottleneck, 7x7/2 stem + 3x3/2 max-pool).

Straightforward `lax.conv_general_dilated` + `jax.numpy`, float32, no flax,
no kernels. Train-mode batch norm: statistics over ALL rows of the batch
(the global batch on several chips), biased variance, eps 1e-5.

`arch`: {"block": "bottleneck"|"basic", "stage_sizes": [3,4,6,3],
"num_filters": 64, "stem": "imagenet"|"cifar", "num_classes": 1000}.

Leaf names are the program's key paths joined by "/", so that the runner
can install the same seeded weights there; a renamed leaf in the program
fails that installation loudly. Departures from the paper: none in the
forward pass. Initial weights follow the family's convention (He normal on
fan-out for convolutions, 1/sqrt(fan-in) for the classifier, batch-norm
scale 1 and bias 0) but for the LAST batch norm of every residual block,
whose scale starts at RESIDUAL_SCALE = 0.1: between the program's own 1 and
the zero of Goyal et al. (arXiv:1706.02677). At scale 1 a batch-normed
50-layer net is chaotic at its initial weights: the gradient of the bf16
program and of the float32 reference differ by 76 % of their norm element
by element while their norms agree to 2 %, and no comparison can tell bf16
from fp8 (my chip run, PR 25). At 0 most gradients are exactly zero.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import Spec, quantizer

BN_EPS = 1e-5
RESIDUAL_SCALE = 0.1


def _blocks(arch):
    """(name, in_ch, filters, stride) of every residual block, in order."""
    exp = 4 if arch["block"] == "bottleneck" else 1
    cin = arch["num_filters"]
    for i, n in enumerate(arch["stage_sizes"]):
        f = arch["num_filters"] * 2 ** i
        for j in range(n):
            yield f"backbone/layer{i + 1}_block{j}", cin, f, (2 if i > 0 and j == 0 else 1)
            cin = f * exp


def _block_convs(arch, cin, f):
    """[(k, cin, cout)] of one block's main path."""
    if arch["block"] == "bottleneck":
        return [(1, cin, f), (3, f, f), (1, f, 4 * f)]
    return [(3, cin, f), (3, f, f)]


def param_spec(arch) -> Spec:
    spec: Spec = {}

    def conv(name, k, cin, cout):
        spec[f"{name}/kernel"] = ((k, k, cin, cout), "normal",
                                  math.sqrt(2.0 / (k * k * cout)))

    def bn(name, c, scale=1.0):
        spec[f"{name}/scale"] = ((c,), "const", scale)
        spec[f"{name}/bias"] = ((c,), "zeros", 0.0)

    nf = arch["num_filters"]
    conv("backbone/conv_stem", 7 if arch["stem"] == "imagenet" else 3, 3, nf)
    bn("backbone/bn_stem", nf)
    exp = 4 if arch["block"] == "bottleneck" else 1
    for name, cin, f, stride in _blocks(arch):
        convs = _block_convs(arch, cin, f)
        for n, (k, ci, co) in enumerate(convs):
            conv(f"{name}/Conv_{n}", k, ci, co)
            bn(f"{name}/BatchNorm_{n}", co,
               RESIDUAL_SCALE if n == len(convs) - 1 else 1.0)
        if stride != 1 or cin != f * exp:
            conv(f"{name}/downsample_conv", 1, cin, f * exp)
            bn(f"{name}/downsample_bn", f * exp)
    feat = nf * 2 ** (len(arch["stage_sizes"]) - 1) * exp
    spec["backbone/fc/kernel"] = ((feat, arch["num_classes"]), "normal",
                                  1.0 / math.sqrt(feat))
    spec["backbone/fc/bias"] = ((arch["num_classes"],), "zeros", 0.0)
    return spec


def forward_for(arch, precision: str = "float32"):
    """-> `forward(params, x)`: normalised float32 NHWC rows -> logits.
    Below float32, `q` rounds what a computation in that precision would
    hold in it: the operands of every convolution and matmul and every
    layer's output (convolution, batch norm, the block's sum); the logits
    and the loss stay float32, as in the program."""
    q = quantizer(precision)

    def conv(p, name, x, stride=1):
        w = p[f"{name}/kernel"]
        pad = w.shape[0] // 2
        return q(lax.conv_general_dilated(
            q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")))

    def bn(p, name, x):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
        inv = lax.rsqrt(var + BN_EPS) * p[f"{name}/scale"]
        return q((x - mean) * inv + p[f"{name}/bias"])

    def block(p, x, name, cin, f, stride):
        exp = 4 if arch["block"] == "bottleneck" else 1
        convs = _block_convs(arch, cin, f)
        # the stride sits on the 3x3: the second conv of a bottleneck,
        # the first of a basic block
        strided = 1 if arch["block"] == "bottleneck" else 0
        y = x
        for n in range(len(convs)):
            y = conv(p, f"{name}/Conv_{n}", y, stride if n == strided else 1)
            y = bn(p, f"{name}/BatchNorm_{n}", y)
            if n < len(convs) - 1:
                y = jax.nn.relu(y)
        if stride != 1 or cin != f * exp:
            x = bn(p, f"{name}/downsample_bn",
                   conv(p, f"{name}/downsample_conv", x, stride))
        return q(jax.nn.relu(y + x))

    def forward(p, x):
        imagenet = arch["stem"] == "imagenet"
        x = conv(p, "backbone/conv_stem", x, 2 if imagenet else 1)
        x = jax.nn.relu(bn(p, "backbone/bn_stem", x))
        if imagenet:
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                                  [(0, 0), (1, 1), (1, 1), (0, 0)])
        for name, cin, f, stride in _blocks(arch):
            # recomputed in the backward pass so that float32 activations
            # of the whole batch fit; the arithmetic is the same
            x = jax.checkpoint(
                lambda pp, xx, a=(name, cin, f, stride): block(pp, xx, *a))(p, x)
        x = q(jnp.mean(x, axis=(1, 2)))
        return x @ q(p["backbone/fc/kernel"]) + p["backbone/fc/bias"]

    return forward
