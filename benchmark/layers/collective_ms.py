"""collectives: device milliseconds per step spent in all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute ops on
device 0 (summed durations, hidden or not). Nothing to read on one chip."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or ctx["chips"] < 2:
        return None
    return trace["collective_ms"]
