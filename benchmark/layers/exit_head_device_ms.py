"""exit gate and head: device milliseconds per step of every op under the
program's outermost `exit` scope — what a looped decoder's loss takes from
the R passes' states: the exit gate, its distribution p over the passes and
p's entropy, and inside it under `lm_head` the R x B x T rows through the one
head and the p-weighted sums, forward, rematerialized forward and backward,
over the whole steps of the traced slice (layers/_scope_members.py). The log
lines beside it give the `lm_head` part and the head's analytic FLOPs
(benchmark/flops, forward x 3) over the whole. A program without the scope
gives None."""

from benchmark.layers import _scope_members


def read(ctx):
    ms = _scope_members.scope_ms(ctx, "exit")
    if ms is not None:
        head = _scope_members.scope_ms(ctx, "lm_head")
        if head is not None:
            print(f"[bench] exit: lm_head {head:.3f} of {ms:.3f} ms a step",
                  flush=True)
        _scope_members.log_share(ctx, "exit", ms, "exit_head_flops",
                                 ctx["batch"] // ctx["chips"])
    return ms
