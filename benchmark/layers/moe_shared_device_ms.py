"""expert layer: device milliseconds per step of the ops under the program's
`moe.shared` scope — the shared expert every token takes (gate, up, SiLU,
down; three dense matmuls of 16,384 x 2,048 x 768 a routing layer), forward,
rematerialized forward and backward, over the whole steps of the traced slice
(layers/_scope_members.py). `_scoped_ops.py`'s table counts it in `rest`. The
log line beside it gives its analytic FLOPs (benchmark/flops, forward x 3)
over that time."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "moe.shared")
    if ms is not None:
        _scope_members.log_share(
            ctx, "moe.shared", ms, "shared_flops",
            ctx["batch"] // ctx["chips"] * ctx["arch"]["seq_len"])
    return ms
