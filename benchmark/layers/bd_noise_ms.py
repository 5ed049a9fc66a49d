"""input pipeline: median milliseconds a train batch of the window spends in
the program's `input.noise` spans — the block-diffusion noising of the
batch's rows (data/diffusion.py: the levels, the masked positions, x_t), one
span a row, summed over the rows that fall inside the batch's `input.load`
span (whichever thread loaded them). A program without the span (one that
does not noise its rows, or is older than it) gives None."""

import statistics

from benchmark.layers import _program_spans as ps


def read(ctx):
    w = ps.window(ctx)
    if w is None:
        return None
    from ddp_classification_pytorch_tpu.obs import spans

    noise = sorted((s.start_ns, s.end_ns) for s in spans.snapshot()
                   if s.name == "input.noise")
    loads = w["stages"]["input.load"].values()
    if not noise or not loads:
        return None
    per_batch = [sum(end - start for start, end in noise
                     if load.start_ns <= start and end <= load.end_ns) * 1e-6
                 for load in loads]
    return float(statistics.median(per_batch))
