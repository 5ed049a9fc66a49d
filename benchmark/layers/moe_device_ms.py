"""expert layer: device milliseconds per step of the ops under the program's
`moe.*` scopes — route (router logits, top-k), dispatch (sort, gather),
experts (the grouped matmuls, ReGLU), combine (gather back, weighted sum) —
forward, rematerialized forward and backward, over the whole steps of the
traced slice (layers/_scoped_ops.py)."""

from benchmark.layers import _scoped_ops


def read(ctx):
    return _scoped_ops.scope_ms(ctx, "moe.")
