"""Gated DeltaNet: the recurrence's share of its roofline — the least time
the chip could take for it (benchmark/flops `gdn_core_bound_s`: the larger of
the chunked form's FLOPs at a NOMINAL chunk of 64 over the bf16 peak and of
q, k, v, g, beta in and o out, once, over the HBM's bandwidth; forward x 3,
every Gated DeltaNet layer, the held heads; the bytes bind) over the time of
the op events under `gdn.core` (forward, rematerialized forward and backward;
union of intervals, layers/_scope_members.py; a relayout copy without a scope
path is not in it). Counted from shapes alone, whatever chunk or kernel the
program computes with, so a later kernel is read against the same work. A
program without the scope, or a configuration without the count, gives
None."""

import importlib

from benchmark.layers import _scope_members, _scoped_ops


def read(ctx):
    flops = importlib.import_module(f"benchmark.flops.{ctx['config']['flops']}")
    if not hasattr(flops, "gdn_core_bound_s"):
        return None
    ms = _scope_members.scope_ms(ctx, "gdn.core")
    if ms is None:
        return None
    bound = flops.gdn_core_bound_s(
        ctx["arch"], ctx["batch"] // ctx["chips"],
        _scoped_ops.peak(ctx, "bf16_flops_per_s"),
        _scoped_ops.peak(ctx, "hbm_bytes_per_s"))
    return 100.0 * bound / (ms * 1e-3)
