"""Plain ViT (Dosovitskiy et al., arXiv:2010.11929, table 1): patch
embedding, learned position embedding, pre-LN blocks (LN -> multi-head
self-attention -> residual, LN -> MLP 4x with GELU -> residual), final LN,
linear classifier. Straightforward `jax.numpy`, float32, no flax, no
kernels, dropout 0.

`arch`: {"patch": 16, "dim": 768, "depth": 12, "heads": 12,
"image_size": 224, "num_classes": 1000}.

Departures from the paper, all the program's (its `models/vit.py`), kept
so that both compute the same function and noted here:
- no class token: the classifier reads the MEAN over the patch tokens
  (224 px -> 196 tokens, not 197);
- GELU in its tanh approximation;
- LayerNorm eps 1e-6.
Leaf names are the program's key paths joined by "/" (see resnet.py).
Initial weights by the family's convention: 1/sqrt(fan-in) normal
kernels, zero biases, LN scale 1, position embedding N(0, 0.02).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Spec, quantizer

LN_EPS = 1e-6


def _tokens(arch) -> int:
    return (arch["image_size"] // arch["patch"]) ** 2


def param_spec(arch) -> Spec:
    spec: Spec = {}
    d, pt = arch["dim"], arch["patch"]

    def dense(name, cin, cout):
        spec[f"{name}/kernel"] = ((cin, cout), "normal", 1.0 / math.sqrt(cin))
        spec[f"{name}/bias"] = ((cout,), "zeros", 0.0)

    def ln(name):
        spec[f"{name}/scale"] = ((d,), "ones", 0.0)
        spec[f"{name}/bias"] = ((d,), "zeros", 0.0)

    spec["backbone/patch_embed/kernel"] = (
        (pt, pt, 3, d), "normal", 1.0 / math.sqrt(pt * pt * 3))
    spec["backbone/patch_embed/bias"] = ((d,), "zeros", 0.0)
    spec["backbone/pos_embed"] = ((1, _tokens(arch), d), "normal", 0.02)
    for i in range(arch["depth"]):
        b = f"backbone/block{i}"
        ln(f"{b}/ln1")
        dense(f"{b}/attn/qkv", d, 3 * d)
        dense(f"{b}/attn/proj", d, d)
        ln(f"{b}/ln2")
        dense(f"{b}/mlp_in", d, 4 * d)
        dense(f"{b}/mlp_out", 4 * d, d)
    ln("backbone/ln_final")
    dense("backbone/fc", d, arch["num_classes"])
    return spec


def forward_for(arch, precision: str = "float32"):
    """-> `forward(params, x)`: normalised float32 NHWC rows -> logits.
    Below float32, `q` rounds what a computation in that precision would
    hold in it: every matmul's operands and every layer's output (dense,
    LayerNorm, GELU, the residual sums); logits and loss stay float32."""
    q = quantizer(precision)
    d, heads, pt = arch["dim"], arch["heads"], arch["patch"]
    hd = d // heads

    def dense(p, name, x):
        return q(q(x) @ q(p[f"{name}/kernel"]) + p[f"{name}/bias"])

    def ln(p, name, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return q((x - mean) * jax.lax.rsqrt(var + LN_EPS) * p[f"{name}/scale"]
                 + p[f"{name}/bias"])

    def block(p, x, b):
        n, t, _ = x.shape
        qkv = dense(p, f"{b}/attn/qkv", ln(p, f"{b}/ln1", x))
        qkv = qkv.reshape(n, t, 3, heads, hd)
        qq, kk, vv = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qq), q(kk)) * hd ** -0.5
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", q(a), q(vv)).reshape(n, t, d)
        x = q(x + dense(p, f"{b}/attn/proj", q(o)))
        y = dense(p, f"{b}/mlp_in", ln(p, f"{b}/ln2", x))
        y = q(jax.nn.gelu(y, approximate=True))
        return q(x + dense(p, f"{b}/mlp_out", y))

    def forward(p, x):
        n, h, w, _ = x.shape
        # non-overlapping patches: the stride-`patch` convolution as one matmul
        x = x.reshape(n, h // pt, pt, w // pt, pt, 3).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(n, (h // pt) * (w // pt), pt * pt * 3)
        k = p["backbone/patch_embed/kernel"].reshape(pt * pt * 3, d)
        x = q(q(x) @ q(k) + p["backbone/patch_embed/bias"] + p["backbone/pos_embed"])
        for i in range(arch["depth"]):
            # recomputed in the backward pass so float32 activations fit
            x = jax.checkpoint(
                lambda pp, xx, b=f"backbone/block{i}": block(pp, xx, b))(p, x)
        x = q(jnp.mean(ln(p, "backbone/ln_final", x), axis=1))
        return x @ q(p["backbone/fc/kernel"]) + p["backbone/fc/bias"]

    return forward
