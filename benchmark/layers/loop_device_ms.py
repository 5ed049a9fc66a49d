"""looped stack: device milliseconds per step of every op under the program's
outermost `loop` scope — the R passes of the stack (a looped decoder,
`--loops` R: attention, the gated MLP, the layers' four norms and the final
norm that closes each pass), forward, rematerialized forward and backward,
over the whole steps of the traced slice (layers/_scope_members.py). It cuts
ACROSS `attn_device_ms` by design: the passes' attention counts in both. The
log line beside it gives the layer applications' analytic FLOPs
(benchmark/flops, forward x 3) over that time. A program without the scope
gives None."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "loop")
    if ms is not None:
        _scope_members.log_share(ctx, "loop", ms, "loop_flops",
                                 ctx["batch"] // ctx["chips"])
    return ms
