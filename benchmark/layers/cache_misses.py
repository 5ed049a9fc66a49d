"""launch / CLI: persistent compile-cache misses of this process, from the
program's own `jax.monitoring` counters (`utils/cache.py`). 0 once the
checkout's cache is warm; a miss is a compile that set-up paid for."""


def read(ctx):
    cache = ctx["samples"].get("cache")
    return None if cache is None else float(cache["misses"])
