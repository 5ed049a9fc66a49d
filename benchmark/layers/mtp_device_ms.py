"""multi-token prediction: device milliseconds per step of every op under
the program's outermost `mtp` scope — the module's two norms and projection,
its layer (latent attention, router, experts, shared expert), its final norm
and its pass through the shared head and loss; forward, rematerialized
forward and backward — over the whole steps of the traced slice
(layers/_scope_members.py). It cuts ACROSS the other scopes by design: the
module's attention also counts in `attn_device_ms`, its experts in
`moe_device_ms`. The log line beside it gives the analytic FLOPs of the
module (benchmark/flops, forward x 3) over that time."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "mtp", inherit=True)
    if ms is not None:
        _scope_members.log_share(ctx, "mtp", ms, "mtp_flops",
                                 ctx["batch"] // ctx["chips"])
    return ms
