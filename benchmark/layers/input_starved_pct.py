"""input pipeline: of the window's batches handed to the loop, the share
that was not staged yet when the loop asked — per batch what the program
adds to its `input_starved_total` / `input_batches_total` counters, noted
on the step's `train.input_wait` span as `starved`."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    w = ps.window(ctx)
    flags = [] if w is None else [s.ids.get("starved")
                                  for s in w["stages"]["train.input_wait"].values()]
    if not flags or None in flags:
        return None
    return 100.0 * sum(flags) / len(flags)
