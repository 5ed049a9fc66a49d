"""attention: device milliseconds per step of the ops under the program's
`attn` scope (q/k/v/o projections, rotary, the flash kernels; forward,
rematerialized forward and backward), over the whole steps of the traced
slice. Read from the profile's op events by their scope
(layers/_scoped_ops.py)."""

from benchmark.layers import _scoped_ops


def read(ctx):
    return _scoped_ops.scope_ms(ctx, "attn")
