"""Unified observability spine — dependency-free telemetry for every
subsystem (trainer, serve, fleet, scenario).

Three planes, one package:

- `obs.registry` — Prometheus-style counters/gauges/bounded-window
  histograms with a text-exposition exporter (`/metrics`,
  `$OUT/metrics.prom`) and a JSON snapshot. `serve/metrics.py` is a thin
  bridge over it; the trainer, `parallel/fleet.py`, `train/sentinel.py`
  and `serve/reload.py` register instruments directly.
- `obs.spans` — the span recorder: the one place that records spans of
  work that really ran (set-up phases, the input pipeline's stages, the
  step loop), always on, bounded; read by `Trainer._write_prom`
  (`span_seconds_total{span=…}`) and by the benchmark's per-layer readers.
- `obs.events` — the machine-readable event plane (`events.jsonl`).
  `emit()` stays env-gated and unconditionally cheap.

Everything here is host-side bookkeeping: no instrument ever syncs a
device value or appears inside a jitted program (`analysis/lint.py`
host-sync pass stays green over the instrumented factories).
"""

from . import events, registry, spans  # noqa: F401
from .registry import Registry  # noqa: F401
