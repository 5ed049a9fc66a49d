"""Persistent XLA compilation cache setup (shared by `cli.train`,
`cli.serve`, the benchmark's runners and chip_smoke.py)."""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# The path is part of the cache key, so it must not move between runs:
# fixed inside the checkout, never built from $HOME, a temp name or a pid.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


# what the compiler did in this process, from jax.monitoring's own events:
# entries read from / written to the persistent cache, and the backend
# programs built (compiled, or loaded from that cache) with their seconds
# (chip_smoke.py reads the line the CLIs print from this)
_stats = {"dir": "", "hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0}
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_listening = False


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    def on_event(event, **_):
        if event in _EVENTS:
            _stats[_EVENTS[event]] += 1

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _stats["compiles"] += 1
            _stats["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def compiled() -> "tuple[int, float]":
    """(backend programs built so far in this process, their seconds): read
    it before and after a stretch of set-up to see what that stretch
    compiled. One eager op on the device is one program, so a stretch that
    should be a single jitted call reads 1 to 3 here, not hundreds."""
    _listen()
    return _stats["compiles"], _stats["compile_s"]


def compile_stats_line() -> str:
    return (f"compile cache: dir={_stats['dir'] or 'off'} "
            f"hits={_stats['hits']} misses={_stats['misses']} "
            f"compile_s={_stats['compile_s']:.1f}")


def enable_persistent_cache(min_compile_secs: float = 2.0) -> str:
    """Repeat runs skip the XLA compiles. Returns the cache directory in
    use ("" when the cache is off).

    Where `JAX_COMPILATION_CACHE_DIR` is set the cache is placed from
    outside: JAX reads the variable itself and no directory is set in code.
    Unset, the cache lives at `DEFAULT_CACHE_DIR` inside the checkout.

    CPU is excluded. Observed live (2026-08-04, chaos drill + preemption
    test, deterministic across repeats): an executable DESERIALIZED from
    the persistent cache by a later CPU process computed NaN where the
    freshly compiled executable of the same HLO was finite — the restored
    state was bit-verified identical and the first step's metrics matched
    exactly, then the next step's gradients went NaN — and one such
    process segfaulted at teardown. CPU compiles are seconds, so the
    cache buys little there; it stays on for the TPU, whose minutes-long
    compiles it exists to skip.

    The platform check reads config/env only — it must not trigger the
    first backend initialization (a `--platform` pin lands before it)."""
    import jax

    _listen()
    platforms = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    if platforms.split(",")[0].strip().lower() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return ""
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    _stats["dir"] = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not _stats["dir"]:
        _stats["dir"] = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return _stats["dir"]
