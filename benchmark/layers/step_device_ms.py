"""train step: device milliseconds per step — the union of the intervals
in which an op ran on device 0, over the whole steps of the traced slice,
divided by those steps."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace["step_device_ms"]
