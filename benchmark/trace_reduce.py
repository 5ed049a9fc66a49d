"""From the profiler's trace to numbers, in two steps so that the second
can be tested on a small recorded file:

  extract(xplane.pb) -> intervals   the few lines of the trace that are read
  reduce(intervals)  -> numbers     busy union, steps, collectives, top ops,
                                    idle gaps by what the host was doing

`intervals` is plain JSON:
  {"devices": {"<id>": {"ops": [[name, start_ns, dur_ns], ...],
                        "modules": [[name, start_ns, dur_ns], ...]}},
   "host": [[name, start_ns, dur_ns], ...]}     # the wrappers' spans only

The host spans are taken by the runner on the host clock and moved onto
the trace's clock by `host_on_trace_clock`: a marker program the runner
runs at each end of the slice ends at a known trace time, and the host saw
it end at a known host time.

Device planes are "/device:TPU:<id>"; their "XLA Ops" line holds one event
per executed HLO op and "XLA Modules" one per executed program. HLO names
such as `fusion.123` carry no phase, so nothing here splits forward from
backward: that waits for named scopes in the program.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MARKER = "bench_marker"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?([.\d]*)$")

Interval = Tuple[float, float]


def short_name(name: str) -> str:
    """An op event carries its whole HLO line (`%fusion.12 = bf16[...] fusion(
    ...)`); the name the trace prints for it is what stands before ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(m.group(1), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend([short_name(e.name), e.start_ns, e.duration_ns]
                                    for e in line.events)
    return out


def host_on_trace_clock(intervals: dict, host_spans: Dict[str, list],
                        anchors: List[float]) -> list:
    """[[name, start_ns, dur_ns]] of the host's spans ((start s, seconds) on
    the host clock) on the trace's clock. `anchors` are the host times at
    which the marker programs were seen to end, in order; the trace holds
    their ends on device 0. Nothing to align with => no spans."""
    first = sorted(intervals["devices"], key=int)[0] if intervals["devices"] else None
    if first is None:
        return []
    ends = sorted(s + d for n, s, d in intervals["devices"][first]["modules"]
                  if MARKER in n)
    pairs = list(zip(anchors, ends))
    if not pairs:
        return []
    # host seconds -> trace ns; the host sees an end a little late, never early
    offset_ns = min(h * 1e9 - e for h, e in pairs)
    lo = min(s for d in intervals["devices"].values() for _, s, _ in d["ops"])
    hi = max(s + x for d in intervals["devices"].values() for _, s, x in d["ops"])
    out = []
    for name, spans in host_spans.items():
        for start, seconds in spans:
            s = start * 1e9 - offset_ns
            if s + seconds * 1e9 >= lo and s <= hi:
                out.append([name, s, seconds * 1e9])
    return sorted(out, key=lambda r: r[1])


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The idle stretches of `window` that the merged `busy` leaves."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, min(s, window[1])))
        at = max(at, e)
        if at >= window[1]:
            break
    if at < window[1]:
        out.append((at, window[1]))
    return [(s, e) for s, e in out if e > s]


def _clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    return [(max(s, window[0]), min(e, window[1])) for s, e in intervals
            if e > window[0] and s < window[1]]


def step_module(modules: List[list]) -> Optional[str]:
    """The program that took most device time in the slice: the train step."""
    by = defaultdict(float)
    for name, _, dur in modules:
        if MARKER not in name:
            by[name] += dur
    return max(by, key=by.get) if by else None


def reduce(intervals: dict, chips: int = 1) -> Optional[dict]:
    devices = intervals["devices"]
    ids = sorted(devices, key=int)[:chips]
    if not ids or not any(devices[i]["ops"] for i in ids):
        return None
    ops = {i: [(s, s + d) for _, s, d in devices[i]["ops"]] for i in ids}
    # the slice, on the device's clock: whole steps only, from the start of
    # the first complete step program on device 0 to the end of the last
    first = ids[0]
    name = step_module(devices[first]["modules"])
    steps = sorted((s, s + d) for n, s, d in devices[first]["modules"] if n == name)
    if len(steps) >= 3:
        # the first and the last may be cut by the start and stop of the trace
        steps = steps[1:-1]
    if steps:
        window = (steps[0][0], steps[-1][1])
        # idle after the last step belongs to it: close at the next start
        later = [s for n, s, d in devices[first]["modules"] if n == name and s >= window[1]]
        if later:
            window = (window[0], min(later))
    else:
        flat = [x for i in ids for x in ops[i]]
        window = (min(s for s, _ in flat), max(e for _, e in flat))
    n_steps = max(len(steps), 1)
    busy = {i: union(_clip(ops[i], window)) for i in ids}
    busy_s = sum(total(b) for b in busy.values()) / len(ids) * 1e-9
    window_s = (window[1] - window[0]) * 1e-9

    by_op = defaultdict(float)
    coll_ns = 0.0
    for opname, s, d in devices[first]["ops"]:
        if s >= window[0] and s + d <= window[1]:
            by_op[opname] += d
            m = COLLECTIVE.match(opname)
            if m and m.group(2) != "-start":
                # an async pair is counted once, by its -done half plus any
                # synchronous op; -start only enqueues
                coll_ns += d
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    host = sorted((s, s + d, n) for n, s, d in intervals["host"])
    by_host = defaultdict(float)
    for s, e in gaps(busy[first], window):
        mid = 0.5 * (s + e)
        what = next((n for hs, he, n in host if hs <= mid <= he), "between_spans")
        by_host[what] += (e - s) * 1e-9
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "steps": n_steps,
        "step_module": name,
        "step_device_ms": total(busy[first]) * 1e-6 / n_steps,
        "collective_ms": coll_ns * 1e-6 / n_steps,
        "idle_pct": 100.0 * (1.0 - total(busy[first]) * 1e-9 / window_s),
        "breakdown": {
            "device_ops": [[n, d * 1e-9] for n, d in top],
            "idle_gaps": [[n, s] for n, s in idle],
        },
    }


def cut(intervals: dict, steps: int = 4) -> dict:
    """The first few steps of `intervals`, for a test fixture."""
    first = sorted(intervals["devices"], key=int)[0]
    name = step_module(intervals["devices"][first]["modules"])
    starts = sorted(s for n, s, _ in intervals["devices"][first]["modules"] if n == name)
    if len(starts) <= steps + 2:
        return intervals
    lo, hi = starts[0], starts[steps + 2]
    keep = lambda rows: [r for r in rows if lo <= r[1] < hi]  # noqa: E731
    return {"devices": {i: {"ops": keep(d["ops"]), "modules": keep(d["modules"])}
                        for i, d in intervals["devices"].items()},
            "host": keep(intervals["host"])}


def load_dir(trace_dir: str, host_spans: Optional[Dict[str, list]] = None,
             anchors: Optional[List[float]] = None) -> Optional[dict]:
    """The intervals of the newest trace under `trace_dir`, the wrappers'
    host spans brought onto the trace's clock; None where there is none."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return None
    intervals = extract(paths[-1])
    intervals["host"] = host_on_trace_clock(intervals, host_spans or {}, anchors or [])
    return intervals


def reduce_dir(trace_dir: str, chips: int,
               host_spans: Optional[Dict[str, list]] = None,
               anchors: Optional[List[float]] = None) -> Optional[dict]:
    intervals = load_dir(trace_dir, host_spans, anchors)
    return None if intervals is None else reduce(intervals, chips)
