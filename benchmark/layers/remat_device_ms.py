"""train step: device milliseconds per step of the recomputed forward — the
op events under `checkpoint/rematted_computation` (`--remat`: a decoder
layer is run again in its backward pass but for the flash kernels' saved
outputs, and the head's scan body is; `mfu_pct` counts none of it), over the
whole steps of the traced slice (layers/_phases.py). None where no event is
(a step without `--remat`)."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "remat")
