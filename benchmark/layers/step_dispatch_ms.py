"""train step: median milliseconds of the program's `train.step_dispatch`
span over the window's steps — the loop inside `self.train_step(...)`.
Where the device is the bottleneck this holds the runtime's back-pressure
(the call returns when the queue of steps has room), not only the enqueue."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, "train.step_dispatch")
