"""train step: device milliseconds per step of a ResNet's batch norms — the
op events of kind `bn` in every stage and every phase (statistics, normalize
with the ReLU behind it, their backward), over the whole steps of the traced
slice (layers/_phases.py). A fusion carries its root's `op_name`: a
normalize that the compiler fused into the next convolution counts there."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.block_ms(ctx, r"\w+\.bn")
