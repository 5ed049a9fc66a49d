"""Kimi delta attention: device milliseconds per step of the ops under the
program's `kda` scope — the token mixer of Ling-3.0-flash's linear-attention
layers (`kda.in`: the q/k/v/decay/beta projections, the three depthwise taps,
SiLU, the L2 norms; `kda.core`: the gated delta rule's recurrence in chunks,
plain XLA; `kda.out`: the gated per-head RMSNorm and W_o), forward,
rematerialized forward and backward, over the whole steps of the traced slice
(layers/_scope_members.py). `_scoped_ops.py`'s table counts it in `rest`. The
log lines beside it give the three inner scopes and the blocks' analytic
FLOPs (benchmark/flops, forward x 3) over that time. A program without the
scope gives None."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "kda")
    if ms is not None:
        parts = {s: _scope_members.scope_ms(ctx, s)
                 for s in ("kda.in", "kda.core", "kda.out")}
        print("[bench] kda: " + ", ".join(
            f"{s} {v:.3f}" for s, v in parts.items() if v is not None)
            + " ms a step", flush=True)
        _scope_members.log_share(
            ctx, "kda", ms, "kda_flops",
            ctx["batch"] // ctx["chips"] * ctx["arch"]["seq_len"])
    return ms
