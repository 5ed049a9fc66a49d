"""input pipeline: median milliseconds of the program's `input.load` span
per train batch of the window — the loader's producer thread inside
`_load_batch` (native decode, or per-sample reads and `np.stack`)."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, "input.load")
