"""train step: device milliseconds per step of a ViT's attention modules —
the op events under `blockN/attn` (`qkv`, ops/attention.py's dense op at 196
tokens, `proj`), every phase, over the whole steps of the traced slice
(layers/_phases.py). `attn_device_ms` is the decoder cells' and keeps its
list."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.block_ms(ctx, "attn")
