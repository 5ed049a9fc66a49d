"""Device time of the op events that stand ANYWHERE under a `jax.named_scope`
of the program — the outermost scope counts, where `_scoped_ops.py` puts an
op under its innermost one. Two readers use it: `mtp_device_ms` (everything
the multi-token-prediction module runs: its attention also counts in
`attn_device_ms`, its experts in `moe_device_ms`) and `moe_shared_device_ms`.

It opens the newest `.xplane.pb` of the cell's trace directory itself, reads
every op event's `op_name` path from the event metadata (layers/_xplane.py)
and cuts the slice exactly as `_scoped_ops._cut` does: whole steps of the
step program, the first and the last left out. The compiler's ragged-dot
kernels carry no `op_name`; with `inherit` such an event belongs where the
op before it on the device's line belonged (the device runs one op at a
time, and a grouped matmul follows its own layer's dispatch). A program that
has no such scope (the parent of the PR that added one), or a run without a
trace, gives None: the metric is left out, nothing raises.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark import trace_reduce as tr

_KEY = "_scope_members"


def _load(ctx):
    """([(op_name path or "", start_ns, dur_ns)] inside the slice, steps)
    of device 0, or None."""
    from jax.profiler import ProfileData

    from benchmark.layers import _xplane

    paths = sorted(glob.glob(os.path.join(
        ctx.get("trace_dir", ""), "plugins", "profile", "*", "*.xplane.pb")))
    if ctx.get("trace") is None or not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    planes = sorted((int(m.group(1)), p) for p in data.planes
                    if (m := tr.DEVICE_PLANE.match(p.name)))
    if not planes:
        return None
    plane = planes[0][1]
    meta = _xplane.event_metadata_text(paths[-1], plane.name)
    ops, modules = [], []
    for line in plane.lines:
        if line.name == tr.MODULES_LINE:
            modules = [[tr.short_name(e.name), e.start_ns, e.duration_ns]
                       for e in line.events]
        elif line.name == tr.OPS_LINE:
            ops = [(meta.get(e.name, ""), e.start_ns, e.duration_ns)
                   for e in line.events
                   if not tr.short_name(e.name).startswith("while")]
    name = tr.step_module(modules)
    steps = sorted((s, s + d) for n, s, d in modules if n == name)
    if len(steps) >= 3:
        steps = steps[1:-1]
    if not ops or not steps:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    later = [s for n, s, d in modules if n == name and s >= hi]
    if later:
        hi = min(later)
    ops = sorted((o for o in ops if o[1] >= lo and o[1] + o[2] <= hi),
                 key=lambda o: o[1])
    return ops, len(steps)


def log_share(ctx, label: str, ms: float, counter: str, *args) -> None:
    """One log line: the analytic FLOPs `benchmark/flops/<configuration>.
    <counter>(arch, *args)` (forward x 3) over `ms`, as a share of the chip's
    bf16 peak. Not a metric; a configuration without the counter logs nothing."""
    import importlib

    from benchmark.layers import _scoped_ops

    flops = importlib.import_module(f"benchmark.flops.{ctx['config']['flops']}")
    if not hasattr(flops, counter):
        return
    need = getattr(flops, counter)(ctx["arch"], *args)
    share = 100.0 * need / (ms * 1e-3 * _scoped_ops.peak(ctx, "bf16_flops_per_s"))
    print(f"[bench] {label}: {ms:.3f} ms a step for {need / 1e12:.3f} TFLOP = "
          f"{share:.1f} % of the bf16 peak", flush=True)


def scope_ms(ctx, scope: str, inherit: bool = False):
    """Device ms a step (union of intervals) of the op events whose path
    holds `scope` as a whole segment, or None where no event does."""
    if _KEY not in ctx:
        ctx[_KEY] = _load(ctx)
    if ctx[_KEY] is None:
        return None
    ops, steps = ctx[_KEY]
    segment = re.compile(r"[/(]" + re.escape(scope) + r"(?=[/)]|$)")
    hit, inside = [], False
    for text, start, dur in ops:
        if text or not inherit:
            inside = bool(text) and bool(segment.search(text))
        if inside:
            hit.append((start, start + dur))
    if not hit:
        return None
    return tr.total(tr.union(hit)) * 1e-6 / steps
