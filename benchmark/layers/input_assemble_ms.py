"""input pipeline: median milliseconds of the program's `input.assemble`
span per train batch of the window — the stager thread inside
`make_global_array` (device layout and the host-to-device copy)."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, "input.assemble")
