"""input pipeline: host milliseconds per step that the training loop was
blocked in the prefetcher's `next()`, over the traced slice (the wrapper
around the iterator `Trainer._device_prefetcher` returns)."""


def read(ctx):
    waits = ctx["samples"]["waits"]
    if not waits:
        return None
    return 1e3 * sum(d for _, d in waits) / len(waits)
