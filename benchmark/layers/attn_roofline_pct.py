"""attention: the flash kernels' share of the chip's bf16 peak. Operations:
q k^T and p v over the UNMASKED band of each layer only (full causal
triangle or window band, counted exactly; benchmark/flops), forward x 3 —
the backward kernels' rebuilt score tiles and the rematerialized forward are
not counted. Time: the three Pallas kernels' events under `attn`."""

import importlib

from benchmark.layers import _scoped_ops

KERNEL = r"^flash_(fwd|dq|dkv)"


def read(ctx):
    ms = _scoped_ops.kernel_ms(ctx, "attn", KERNEL)
    if ms is None:
        return None
    flops = importlib.import_module(f"benchmark.flops.{ctx['config']['flops']}")
    need = flops.attention_flops(ctx["arch"], ctx["batch"] // ctx["chips"])
    return 100.0 * need / (ms * 1e-3 * _scoped_ops.peak(ctx, "bf16_flops_per_s"))
