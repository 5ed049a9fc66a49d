"""device: share of the traced slice in which no op ran on device 0."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace["idle_pct"]
