"""train step: device milliseconds per step of the forward pass — the op
events whose `op_name` path autodiff marked `jvp(` and neither `transpose(`
nor `rematted_computation` (model and loss; the first pass only, also where
`--remat` runs a second), over the whole steps of the traced slice
(layers/_phases.py). The step's own scopes (`step.*`) are not in it."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "fwd")
