"""launch / CLI: seconds of the program's `setup.init_state` span —
`create_train_state` inside `Trainer.__init__` (model.init, optimizer
state, placement on the mesh)."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    return ps.seconds_of(ctx, "setup", "setup.init_state")
