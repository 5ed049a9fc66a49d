"""train step: model FLOP/s utilisation of the time the device was busy —
analytic FLOPs of one trained image (forward x 3, nothing recomputed
counted; benchmark/flops/<name>.py) x the chip's rows of the batch, over
`step_device_ms` x the chip's bf16 peak (benchmark/peaks.json). An unknown
device kind is an error, not a default."""

import importlib
import json
import os


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if ctx["device_kind"] not in peaks:
        raise SystemExit(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    flops = importlib.import_module(f"benchmark.flops.{ctx['config']['flops']}")
    per_image = flops.train_flops_per_image(ctx["arch"], ctx["image_size"])
    per_chip = per_image * ctx["batch"] / ctx["chips"]
    return 100.0 * per_chip / (trace["step_device_ms"] * 1e-3
                               * peaks[ctx["device_kind"]]["bf16_flops_per_s"])
