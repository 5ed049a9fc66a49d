"""Profiler step-time breakdown: Chrome-trace parsing into
`{fwd, bwd, optimizer, collectives, h2d, idle}` milliseconds per step.

`jax.profiler.start_trace` writes, next to the xplane protobuf, a
`*.trace.json.gz` in Chrome trace-event format: complete ('X') events
with microsecond `ts`/`dur` on per-thread lanes, including one span per
`jax.profiler.StepTraceAnnotation` window. The parser here:

1. finds the step windows (events named with the step marker, carrying
   `step_num`);
2. clips every other classified event to each window and unions the
   intervals PER LANE AND BUCKET (nested events — a fusion inside a
   module span — must not double-count);
3. buckets by op-name keywords (`classify`); anything unrecognized is
   deliberately NOT guessed — unaccounted window time lands in `idle`,
   so the six buckets always sum to the step wall time exactly.

The per-lane interval union makes the breakdown K-accumulation-proof:
under `parallel.grad_accum` K > 1 one StepTraceAnnotation window (one
OPTIMIZER step) contains K scanned fwd/bwd microbatch executions and a
single deferred gradient reduction — K disjoint same-lane fwd spans sum,
nested/overlapping ones union, and the six buckets still cover the wall
time exactly. The amortized collective lane is the visible win: one
reduction's microseconds per window instead of K of them
(tests/test_obs.py::test_parse_accum_window_buckets_and_amortization).

The CPU-safe fallback is `SpanRecorder`: bench's sub-program probes (a
forward-only and a forward+backward compile of the SAME loss — see
train/steps.py::make_phase_probes) yield host-measured phase durations,
which the recorder lays out as synthetic Chrome-trace events around the
same step markers. Parser and schema are therefore exercised end-to-end
in tier-1 with no accelerator and no profiler (tests/test_obs.py, plus a
checked-in fixture of a real CPU capture).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

# bucket order is the emission order in every row/report
BUCKETS = ("fwd", "bwd", "optimizer", "collectives", "h2d", "idle")

# the StepTraceAnnotation name bench uses for its timed window
STEP_MARKER = "bench_step"

# keyword → bucket, matched lowercase-substring in THIS order: collectives
# and transfers first (their names are unambiguous), then backward (autodiff
# scopes name transposed ops), then optimizer, then forward. An op matching
# nothing is left unclassified → idle, never guessed.
_KEYWORDS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("collectives", ("all-reduce", "allreduce", "all-gather", "allgather",
                     "reduce-scatter", "reducescatter", "all-to-all",
                     "alltoall", "collective-permute", "psum", "ppermute",
                     "collectivebroadcast")),
    ("h2d", ("transfertodevice", "transferhtod", "h2d", "infeed",
             "copy-start", "copy-done", "bufferfromhost")),
    ("bwd", ("backward", "bwd", "transpose(", "grad")),
    ("optimizer", ("optimizer", "apply_updates", "opt_update", "adamw",
                   "adam", "sgd", "lamb", "momentum")),
    ("fwd", ("forward", "fwd")),
)


def classify(name: str) -> Optional[str]:
    """Bucket for one trace-event name, or None (→ idle) when unknown.
    Exact bucket names map to themselves first — that is the contract the
    SpanRecorder's synthetic events rely on."""
    low = name.lower()
    if low in BUCKETS:
        return low
    for bucket, needles in _KEYWORDS:
        for needle in needles:
            if needle in low:
                return bucket
    return None


def _union_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered microseconds of possibly-overlapping intervals."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_lo, cur_hi = intervals[0]
    for lo, hi in intervals[1:]:
        if lo > cur_hi:
            total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    total += cur_hi - cur_lo
    return total


def parse_chrome_trace(trace: Dict[str, Any],
                       step_marker: str = STEP_MARKER) -> List[Dict]:
    """Per-step breakdowns from a Chrome trace-event object.

    Returns one dict per step window, sorted by step number:
    `{"step": n, "step_ms": wall, "fwd": ms, ..., "idle": ms}` with the
    six buckets summing to `step_ms` exactly (idle is the remainder,
    clamped at 0 when classified lanes overlap past the wall)."""
    events = [e for e in trace.get("traceEvents", [])
              if isinstance(e, dict) and e.get("ph") == "X"
              and "ts" in e and "dur" in e]
    markers = [e for e in events if e.get("name") == step_marker]
    out: List[Dict] = []
    for i, m in enumerate(markers):
        lo, hi = float(m["ts"]), float(m["ts"]) + float(m["dur"])
        if hi <= lo:
            continue
        args = m.get("args") or {}
        step_num = args.get("step_num", i)
        try:
            step_num = int(step_num)
        except (TypeError, ValueError):
            step_num = i
        # (lane, bucket) → clipped intervals; the union per lane stops a
        # nested same-bucket event (fusion inside a named scope) from
        # counting its microseconds twice
        lanes: Dict[Tuple[Any, Any, str], List[Tuple[float, float]]] = {}
        for e in events:
            if e is m or e.get("name") == step_marker:
                continue
            bucket = classify(str(e.get("name", "")))
            if bucket is None or bucket == "idle":
                continue
            s, d = float(e["ts"]), float(e["dur"])
            clip_lo, clip_hi = max(s, lo), min(s + d, hi)
            if clip_hi <= clip_lo:
                continue
            key = (e.get("pid"), e.get("tid"), bucket)
            lanes.setdefault(key, []).append((clip_lo, clip_hi))
        sums_us = {b: 0.0 for b in BUCKETS}
        for (_, _, bucket), intervals in lanes.items():
            sums_us[bucket] += _union_us(intervals)
        wall_us = hi - lo
        accounted = sum(sums_us[b] for b in BUCKETS if b != "idle")
        sums_us["idle"] = max(wall_us - accounted, 0.0)
        row = {"step": step_num, "step_ms": wall_us / 1e3}
        row.update({b: sums_us[b] / 1e3 for b in BUCKETS})
        out.append(row)
    out.sort(key=lambda r: r["step"])
    return out


def aggregate(steps: Sequence[Dict], ndigits: int = 3) -> Dict[str, float]:
    """Mean per-bucket milliseconds across step windows → the
    `step_breakdown_ms` dict bench emits ({} when no steps parsed)."""
    if not steps:
        return {}
    n = len(steps)
    out = {b: round(sum(s[b] for s in steps) / n, ndigits) for b in BUCKETS}
    out["step_ms"] = round(sum(s["step_ms"] for s in steps) / n, ndigits)
    out["n_steps"] = n
    return out


# ------------------------------------------------------------ trace files --

def find_trace_file(log_dir: str) -> Optional[str]:
    """Newest Chrome-trace JSON under a jax.profiler log dir (layout:
    `<dir>/plugins/profile/<timestamp>/<host>.trace.json.gz`)."""
    pats = (os.path.join(log_dir, "**", "*.trace.json.gz"),
            os.path.join(log_dir, "**", "*.trace.json"))
    hits = [p for pat in pats for p in glob.glob(pat, recursive=True)]
    return max(hits, key=os.path.getmtime) if hits else None


def load_chrome_trace(path: str) -> Dict[str, Any]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def breakdown_from_trace_dir(log_dir: str,
                             step_marker: str = STEP_MARKER) -> List[Dict]:
    """Parse the newest capture under `log_dir` into per-step breakdowns
    ([] when no trace landed — a disabled or unsupported profiler)."""
    path = find_trace_file(log_dir)
    if path is None:
        return []
    try:
        return parse_chrome_trace(load_chrome_trace(path), step_marker)
    except (OSError, ValueError):
        return []


# ---------------------------------------------------------- span recorder --

class SpanRecorder:
    """Host-side spans in Chrome-trace shape — the CPU-safe fallback.

    Bench's probe decomposition measures phase durations on the host
    (forward-only vs forward+backward vs full-step sub-programs) and
    records them here per step; `to_chrome_trace()` lays the phases out
    sequentially inside a synthetic step-marker window, so the SAME
    parser that reads a real capture produces the emitted breakdown —
    one schema, one code path, fully testable without an accelerator."""

    def __init__(self, step_marker: str = STEP_MARKER):
        self.step_marker = step_marker
        self._steps: List[Tuple[int, float, Dict[str, float]]] = []

    def add_step(self, step_num: int, step_s: float,
                 phases: Dict[str, float]) -> None:
        """Record one step: wall seconds + per-phase seconds (phase names
        must be bucket names; unknown names raise — a typo here would
        silently become idle)."""
        for name in phases:
            if name not in BUCKETS or name == "idle":
                raise ValueError(f"unknown phase {name!r}; one of "
                                 f"{[b for b in BUCKETS if b != 'idle']}")
        self._steps.append((int(step_num), float(step_s), dict(phases)))

    def to_chrome_trace(self) -> Dict[str, Any]:
        events: List[Dict] = []
        cursor = 0.0
        for step_num, step_s, phases in self._steps:
            wall_us = step_s * 1e6
            events.append({"ph": "X", "name": self.step_marker,
                           "pid": 1, "tid": 0, "ts": cursor,
                           "dur": wall_us, "args": {"step_num": step_num}})
            t = cursor
            for name, dur_s in phases.items():
                # clip: a probe mis-measurement must not spill into the
                # next step's window
                dur_us = min(dur_s * 1e6, cursor + wall_us - t)
                if dur_us <= 0:
                    continue
                events.append({"ph": "X", "name": name, "pid": 1, "tid": 0,
                               "ts": t, "dur": dur_us})
                t += dur_us
            cursor += wall_us + 1.0  # 1 µs gap between step windows
        return {"displayTimeUnit": "ns", "traceEvents": events}

    def breakdown(self) -> List[Dict]:
        return parse_chrome_trace(self.to_chrome_trace(), self.step_marker)
