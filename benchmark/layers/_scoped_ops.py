"""What the readers of the decoder's device time share: the op events of
device 0 in the traced slice, each put under the `jax.named_scope` the
program gave the code it came from (`attn`, `moe.route`, `moe.dispatch`,
`moe.experts`, `moe.combine`, `lm_head`; models/decoder_lm.py, ops/moe.py).

`ctx["trace"]` holds only totals, so this opens the newest `.xplane.pb` of
the cell's trace directory itself and reads every event's metadata: the
scope stands in the op's `op_name` path ("jit(step)/.../layer2/attn/q/
dot_general"), which the profile carries among the stats of the event's
metadata (layers/_xplane.py reads those). The
slice is cut exactly as `trace_reduce.reduce` cuts it (whole steps of the
step program). One `[bench] device time by scope` table is logged from it,
with what no scope of these claims as `rest`, so that every op event is
attributed. A program without these scopes, or a run without a trace, gives
None everywhere: the metric is left out, nothing raises.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from benchmark import trace_reduce as tr

SCOPES = ("attn", "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "lm_head")
_KEY = "_scoped_ops"
_SEGMENT = re.compile(
    r"[/(](" + "|".join(re.escape(s) for s in SCOPES) + r")(?=[/)]|$)")


def scope_of(text: str):
    """The innermost of SCOPES in an op's metadata, or None."""
    found = _SEGMENT.findall(text)
    return found[-1] if found else None


def _events(path: str):
    """[(name, scope, start_ns, dur_ns)] of device 0's op line, and its
    module line as trace_reduce has it."""
    from jax.profiler import ProfileData

    from benchmark.layers import _xplane

    data = ProfileData.from_file(path)
    planes = sorted((int(m.group(1)), p) for p in data.planes
                    if (m := tr.DEVICE_PLANE.match(p.name)))
    if not planes:
        return [], []
    # an op event's stats are its timing; its op_name stands in the stats of
    # the event's metadata, which ProfileData does not hand out
    meta = _xplane.event_metadata_text(path, planes[0][1].name)
    ops, modules = [], []
    for line in planes[0][1].lines:
        if line.name == tr.MODULES_LINE:
            modules = [[tr.short_name(e.name), e.start_ns, e.duration_ns]
                       for e in line.events]
        elif line.name == tr.OPS_LINE:
            for e in line.events:
                name = tr.short_name(e.name)
                if name.startswith("while"):
                    continue  # a loop's own event spans its body's ops
                # the compiler's ragged-dot kernels carry no op_name; only
                # the expert layer's grouped matmuls lower to them
                scope = ("moe.experts" if name.startswith("ragged-dot")
                         else scope_of(meta.get(e.name, "")))
                ops.append((name, scope, e.start_ns, e.duration_ns))
    return ops, modules


def table(ctx):
    """{"steps": n, "ms": {scope | "rest": ms a step}, "kernel_ms": {name
    pattern: ms a step}, "all_ms": ms a step} over the slice, or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _cut(ctx)
    return ctx[_KEY]


def _cut(ctx):
    paths = sorted(glob.glob(os.path.join(
        ctx.get("trace_dir", ""), "plugins", "profile", "*", "*.xplane.pb")))
    if ctx.get("trace") is None or not paths:
        return None
    ops, modules = _events(paths[-1])
    name = tr.step_module(modules)
    steps = sorted((s, s + d) for n, s, d in modules if n == name)
    if len(steps) >= 3:
        steps = steps[1:-1]
    if not ops or not steps or not any(scope for _, scope, _, _ in ops):
        return None
    lo, hi = steps[0][0], steps[-1][1]
    later = [s for n, s, d in modules if n == name and s >= hi]
    if later:
        hi = min(later)
    n = len(steps)
    by_scope, by_name = defaultdict(list), defaultdict(float)
    for op, scope, s, d in ops:
        if s >= lo and s + d <= hi:
            by_scope[scope or "rest"].append((s, s + d))
            by_name[(scope or "rest", re.sub(r"[.\d]+$", "", op))] += d
    ms = {k: tr.total(tr.union(v)) * 1e-6 / n for k, v in by_scope.items()}
    all_ms = tr.total(tr.union([x for v in by_scope.values() for x in v])) * 1e-6 / n
    out = {"steps": n, "ms": ms, "all_ms": all_ms,
           "by_name": {k: v * 1e-6 / n for k, v in by_name.items()}}
    _log(out)
    return out


def scope_ms(ctx, prefix: str):
    """Device ms a step of every scope that starts with `prefix`."""
    t = table(ctx)
    if t is None:
        return None
    hit = [v for k, v in t["ms"].items() if k.startswith(prefix)]
    return float(sum(hit)) if hit else None


def kernel_ms(ctx, prefix: str, pattern: str):
    """Device ms a step of the ops in scopes `prefix*` whose HLO name
    matches `pattern` (a kernel's own events)."""
    t = table(ctx)
    if t is None:
        return None
    hit = [v for (scope, op), v in t["by_name"].items()
           if scope.startswith(prefix) and re.search(pattern, op)]
    return float(sum(hit)) if hit else None


def peak(ctx, key: str) -> float:
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if ctx["device_kind"] not in peaks:
        raise SystemExit(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    return peaks[ctx["device_kind"]][key]


def _log(t) -> None:
    def log(msg):
        print(f"[bench] {msg}", flush=True)

    log(f"device time by scope (ms a step over {t['steps']} steps; union of "
        f"the scope's op events):")
    for k, v in sorted(t["ms"].items(), key=lambda kv: -kv[1]):
        log(f"  {k} {v:.3f}")
    log(f"  sum of the scopes {sum(t['ms'].values()):.3f}; union of all op "
        f"events {t['all_ms']:.3f}")
    top = sorted(t["by_name"].items(), key=lambda kv: -kv[1])[:16]
    log("  largest ops (scope, HLO name, ms a step): "
        + "; ".join(f"{s} {n} {v:.3f}" for (s, n), v in top))
