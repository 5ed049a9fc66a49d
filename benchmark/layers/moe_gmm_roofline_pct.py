"""expert layer: the grouped expert matmuls' share of the chip's bf16 peak.
Operations: gate, up and down over the token-slots the step's own counters
say the held experts took (`moe_load`, mean over the window's steps, all
layers), forward x 3 (benchmark/flops: a rematerialized forward is not
counted twice). Time: the ragged-dot kernels' events under `moe.experts`.
At 1,536 rows an expert the matmuls are compute-bound (arithmetic intensity
about 500 FLOP a byte against the chip's 240), so the peak is the FLOP one."""

import importlib

import numpy as np

from benchmark.layers import _scoped_ops

KERNEL = r"^ragged-dot(?!-metadata)"


def read(ctx):
    ms = _scoped_ops.kernel_ms(ctx, "moe.experts", KERNEL)
    loads = ctx["samples"].get("moe_load") or []
    if ms is None or not loads:
        return None
    flops = importlib.import_module(f"benchmark.flops.{ctx['config']['flops']}")
    slots = float(np.mean([x.sum() for x in loads])) / ctx["chips"]
    need = flops.gmm_flops(slots, ctx["arch"])
    return 100.0 * need / (ms * 1e-3 * _scoped_ops.peak(ctx, "bf16_flops_per_s"))
