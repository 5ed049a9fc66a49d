"""Gated DeltaNet: device milliseconds per step of the ops under the
program's `gdn` scope — the token mixer of Olmo-Hybrid's linear-attention
layers (`gdn.in`: the q/k/v/decay/beta projections, the three depthwise taps,
SiLU, the L2 norms, g and beta; `gdn.core`: the gated delta rule's recurrence
with one decay a head, in chunks, plain XLA; `gdn.out`: the SiLU gate's
projection, the gated per-head RMSNorm and W_o), forward, rematerialized
forward and backward, over the whole steps of the traced slice
(layers/_scope_members.py). A relayout copy the compiler puts between two of
these ops carries no scope path and is not in it. `_scoped_ops.py`'s table
counts the scope in `rest`. The log lines beside it give the three inner
scopes and the blocks' analytic FLOPs (benchmark/flops, forward x 3) over
that time. A program without the scope gives None."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "gdn")
    if ms is not None:
        parts = {s: _scope_members.scope_ms(ctx, s)
                 for s in ("gdn.in", "gdn.core", "gdn.out")}
        print("[bench] gdn: " + ", ".join(
            f"{s} {v:.3f}" for s, v in parts.items() if v is not None)
            + " ms a step", flush=True)
        _scope_members.log_share(
            ctx, "gdn", ms, "gdn_flops",
            ctx["batch"] // ctx["chips"] * ctx["arch"]["seq_len"])
    return ms
