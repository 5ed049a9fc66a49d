"""input pipeline: 90th percentile, over the window's steps, of the
program's `train.input_wait` span (the loop blocked in the staged-batch
iterator's `next()`): the per-step tail that `input_wait_ms`'s mean hides."""

from benchmark.layers import _program_spans as ps


def read(ctx):
    values = ps.stage_ms(ctx, "train.input_wait")
    if not values:
        return None
    print(f"[bench] input_wait_p90_ms over {len(values)} steps", flush=True)
    return ps.p90(values)
