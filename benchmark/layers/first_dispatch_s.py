"""launch / CLI: seconds of the program's `train.step_dispatch` span of
step 0 — the train step traced, lowered, and compiled or loaded from the
compile cache. Under the benchmark the span also holds what the runner's
wrapper around `train_step` does at step 0 (its own small programs)."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    return ps.seconds_of(ctx, "first", "train.step_dispatch")
