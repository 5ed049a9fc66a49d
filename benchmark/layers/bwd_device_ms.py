"""train step: device milliseconds per step of the backward pass — the op
events whose `op_name` path holds `transpose(` and no `rematted_computation`
(a `custom_vjp`'s backward rule and a scan's body keep the mark), over the
whole steps of the traced slice (layers/_phases.py)."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "bwd")
