"""short convolution: device milliseconds per step of the ops under the
program's `conv` scope — LFM2's gated short convolution, which stands where
attention stands in most layers (`conv.in`: W_in, one matmul of 16,384 x 2,048
x 6,144 a layer; `conv.mix`: the two gates and the three taps, elementwise
over (16,384, 6,144); `conv.out`: W_out), forward, rematerialized forward and
backward, over the whole steps of the traced slice (layers/_scope_members.py).
`_scoped_ops.py`'s table counts it in `rest`. The log line beside it gives
the two matmuls' analytic FLOPs (benchmark/flops, forward x 3; the taps are
not in them) over that time. A program without the scope gives None."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "conv")
    if ms is not None:
        _scope_members.log_share(
            ctx, "conv", ms, "conv_flops",
            ctx["batch"] // ctx["chips"] * ctx["arch"]["seq_len"])
    return ms
