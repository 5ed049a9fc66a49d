"""Flax ResNet zoo — ImageNet and CIFAR variants, depths 18/34/50/101/152.

Capability parity with the reference's two hand-written zoos
(NESTED/model/imagenet_resnet.py:31-225 — 7×7/2 stem + maxpool, torchvision
topology; NESTED/model/cifar_resnet.py:11-160 — 3×3/1 stem, conv2_x stride 1)
and the torchvision/timm backbones used by BASELINE/ARCFACE/CDR
(BASELINE/main.py:134-144, CDR/main.py:330-338).

TPU-first design decisions (not translations):
- NHWC layout and bf16 compute dtype: XLA:TPU's native conv layout; params and
  BatchNorm statistics stay float32 for numerical stability.
- BatchNorm under `jit` with a batch-sharded input computes *global* batch
  statistics automatically — XLA inserts the cross-replica collectives — so the
  reference's SyncBatchNorm conversion (BASELINE/main.py:148) has no analogue
  here; it is the default semantics. An optional `axis_name` supports the
  shard_map/pmap path.
- No Python control flow depends on data; the whole model traces to one XLA
  computation.
- Every op has a name in a device profile: flax writes the module path into
  `op_name` (`layer1_block0/Conv_0`, `.../BatchNorm_1`, `bn_stem`, `fc`), and
  what stands outside every module gets a `jax.named_scope`: `bn` (the ReLU
  behind a BatchNorm: XLA fuses it into the normalize, and a fusion is named
  after its root), `residual` (add + ReLU), `pool` (the stem's max-pool), and
  `head` around the global pool and `fc` (docs/observability.md, Device-side
  names).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any

FEAT_DIMS = {
    "resnet18": 512,
    "resnet34": 512,
    "resnet50": 2048,
    "resnet101": 2048,
    "resnet152": 2048,
}


def _relu(y: jnp.ndarray) -> jnp.ndarray:
    """The ReLU behind a BatchNorm, under the `bn` scope."""
    with jax.named_scope("bn"):
        return nn.relu(y)


class BasicBlock(nn.Module):
    """3×3 + 3×3 residual block (imagenet_resnet.py:31-60, cifar_resnet.py:11-45)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    expansion: int = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = _relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.ones)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * self.expansion, (1, 1),
                strides=(self.strides, self.strides), name="downsample_conv",
            )(x)
            residual = self.norm(name="downsample_bn")(residual)
        with jax.named_scope("residual"):
            return nn.relu(y + residual)


class Bottleneck(nn.Module):
    """1×1 → 3×3 → 1×1 block, expansion 4 (imagenet_resnet.py:63-99)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    expansion: int = 4

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = _relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = _relu(self.norm()(y))
        y = self.conv(self.filters * self.expansion, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.ones)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * self.expansion, (1, 1),
                strides=(self.strides, self.strides), name="downsample_conv",
            )(x)
            residual = self.norm(name="downsample_bn")(residual)
        with jax.named_scope("residual"):
            return nn.relu(y + residual)


class ResNet(nn.Module):
    """ResNet backbone → pooled feature vector, optional classifier head.

    `num_classes=0` returns the flat feature (the NetFeat role,
    NESTED/model/model.py:12-61); otherwise a final Dense maps to logits
    (the torchvision `fc` role, BASELINE/main.py:136-139).

    cifar_stem=True: 3×3/1 stem, no maxpool, conv2_x stride 1
    (cifar_resnet.py:85-95); else 7×7/2 stem + 3×3/2 maxpool
    (imagenet_resnet.py:108-112).
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 0
    num_filters: int = 64
    cifar_stem: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    axis_name: Optional[str] = None
    freeze_bn: bool = False  # NESTED freeze-BN (model/model.py:44-55)
    bn_momentum: float = 0.9  # torch BN momentum 0.1 == flax momentum 0.9
    # rematerialize residual blocks in the backward pass: trades ~1 extra
    # forward of FLOPs for O(depth) activation memory — the HBM lever for
    # large global batches (jax.checkpoint per block)
    remat: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        def conv(features, kernel_size, strides=(1, 1), **kw):
            # torch-equivalent explicit padding (k//2 both sides): identical to
            # SAME at stride 1, but at stride 2 SAME pads asymmetrically and
            # shifts the grid — explicit padding keeps imported torchvision
            # weights numerically exact (imagenet_resnet.py pad semantics)
            k = kernel_size[0]
            return nn.Conv(
                features, kernel_size, strides=strides, use_bias=False,
                dtype=self.dtype, padding=[(k // 2, k // 2)] * 2,
                kernel_init=nn.initializers.variance_scaling(
                    2.0, "fan_out", "truncated_normal"),
                **kw,
            )
        use_running = (not train) or self.freeze_bn
        norm = functools.partial(
            nn.BatchNorm, use_running_average=use_running,
            momentum=self.bn_momentum, epsilon=1e-5, dtype=self.dtype,
            axis_name=self.axis_name if (train and not self.freeze_bn) else None,
        )

        x = x.astype(self.dtype)
        if self.cifar_stem:
            x = conv(self.num_filters, (3, 3), name="conv_stem")(x)
        else:
            x = conv(self.num_filters, (7, 7), strides=(2, 2), name="conv_stem")(x)
        x = _relu(norm(name="bn_stem")(x))
        if not self.cifar_stem:
            # torch MaxPool2d(3, 2, padding=1); flax max_pool pads with -inf,
            # matching torch's border semantics
            with jax.named_scope("pool"):
                x = nn.max_pool(x, (3, 3), strides=(2, 2),
                                padding=[(1, 1), (1, 1)])

        block_cls = nn.remat(self.block_cls) if self.remat else self.block_cls
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if (i > 0 and j == 0) else 1
                x = block_cls(
                    filters=self.num_filters * (2 ** i),
                    strides=strides, conv=conv, norm=norm,
                    name=f"layer{i + 1}_block{j}",
                )(x)

        # global average pool (adaptive, any input size); f32 output — the
        # pool feeds the f32 head, so rounding the mean back to the compute
        # dtype would only discard mantissa bits in between (dtype audit D6)
        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2), dtype=jnp.float32)
            if self.num_classes > 0:
                x = nn.Dense(self.num_classes, dtype=jnp.float32, name="fc")(x)
        return x


_DEPTHS: dict[str, Tuple[ModuleDef, Sequence[int]]] = {
    "resnet18": (BasicBlock, (2, 2, 2, 2)),
    "resnet34": (BasicBlock, (3, 4, 6, 3)),
    "resnet50": (Bottleneck, (3, 4, 6, 3)),
    "resnet101": (Bottleneck, (3, 4, 23, 3)),
    "resnet152": (Bottleneck, (3, 8, 36, 3)),
}


def _factory(name: str) -> Callable[..., ResNet]:
    block_cls, stages = _DEPTHS[name]

    def make(num_classes: int = 0, variant: str = "imagenet", **kw: Any) -> ResNet:
        return ResNet(
            stage_sizes=stages, block_cls=block_cls, num_classes=num_classes,
            cifar_stem=(variant == "cifar"), **kw,
        )

    make.__name__ = name
    return make


resnet18 = _factory("resnet18")
resnet34 = _factory("resnet34")
resnet50 = _factory("resnet50")
resnet101 = _factory("resnet101")
resnet152 = _factory("resnet152")
