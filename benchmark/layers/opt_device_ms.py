"""train step: device milliseconds per step between the gradient and the new
state — the op events under the step's `step.guard` (global gradient norm,
the two `isfinite`, the keep-selects) and `step.opt` (`tx.update` and
`apply_updates`) scopes (train/steps.py::STEP_SCOPES), over the whole steps
of the traced slice (layers/_phases.py)."""

from benchmark.layers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "opt")
