"""What the readers of the program's own spans share (`obs/spans.py` of the
package under test; the readers run in its process, after the window).

The recorder's ring is taken once per run and cut to the window: the newest
`train.epoch` and, within it, the train batches `k >= warmup_steps`
(`step` is the batch's index in the epoch: the same integer on the loader's,
the stager's and the loop's spans). One `[bench] program spans` table is
logged from it, so that every span has a reader. A program that has no
recorder (older than it), or one whose ring holds no epoch, gives None
everywhere: the metric is left out of the line, nothing raises.
"""

from __future__ import annotations

import statistics

import numpy as np

STAGES = ("input.load", "input.assemble", "train.input_wait",
          "train.step_dispatch", "train.log_sync")
_KEY = "_program_spans"


def ms(span) -> float:
    return (span.end_ns - span.start_ns) * 1e-6


def window(ctx):
    """{"stages": {name: {step: span}} of the window's steps, "first": {name:
    span} of step 0, "setup": {name: span}, "self_ms": [...]} or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _cut(ctx)
        if ctx[_KEY] is not None:
            _log_table(ctx, ctx[_KEY])
    return ctx[_KEY]


def _cut(ctx):
    try:
        from ddp_classification_pytorch_tpu.obs import spans
    except ImportError:  # the program is older than its recorder
        return None
    ring = spans.snapshot()
    epochs = [s for s in ring if s.name == "train.epoch"]
    if not epochs:
        return None
    epoch = epochs[-1]
    warmup = int(ctx["config"]["warmup_steps"])
    stages = {name: {} for name in STAGES}
    first = {}
    for s in ring:
        if s.name not in stages or not epoch.start_ns <= s.start_ns <= epoch.end_ns:
            continue
        if s.ids.get("loader", "train") != "train":
            continue
        k = s.ids["step"]
        if k == 0:
            first[s.name] = s
        if k >= warmup:
            stages[s.name][k] = s
    # what an iteration of the loop spends outside its child spans: from one
    # next() to the following one, less the three children
    waits = stages["train.input_wait"]
    self_ms = []
    for k in sorted(waits):
        if k + 1 in waits and k in stages["train.step_dispatch"]:
            inside = ms(waits[k]) + ms(stages["train.step_dispatch"][k])
            if k in stages["train.log_sync"]:
                inside += ms(stages["train.log_sync"][k])
            self_ms.append((waits[k + 1].start_ns - waits[k].start_ns) * 1e-6 - inside)
    setup = {s.name: s for s in ring if s.name.startswith("setup.")}
    return {"stages": stages, "first": first, "setup": setup, "epoch": epoch,
            "self_ms": self_ms}


def stage_ms(ctx, name: str) -> list:
    """Milliseconds of every span `name` of the window's steps ([] if none)."""
    w = window(ctx)
    return [] if w is None else [ms(s) for _, s in sorted(w["stages"][name].items())]


def median_ms(ctx, name: str):
    values = stage_ms(ctx, name)
    return float(statistics.median(values)) if values else None


def p90(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 90))


def seconds_of(ctx, group: str, name: str):
    """Seconds of the one span `name` in `group` ("setup" / "first")."""
    w = window(ctx)
    if w is None or name not in w[group]:
        return None
    return ms(w[group][name]) * 1e-3


def _log_table(ctx, w) -> None:
    def log(msg):
        print(f"[bench] {msg}", flush=True)

    log("program spans (the window's steps of the newest train.epoch; ms): "
        "name count median p90 max")
    rows = [(name, [ms(s) for s in w["stages"][name].values()]) for name in STAGES]
    rows.append(("train.epoch self/step", w["self_ms"]))
    for name, values in rows:
        if values:
            log(f"  {name} {len(values)} {statistics.median(values):.3f} "
                f"{p90(values):.3f} {max(values):.3f}")
        else:
            log(f"  {name} 0")
    log(f"  train.epoch 1 {ms(w['epoch']):.1f} (whole epoch, warm-up included)")
    for name, s in sorted(w["setup"].items(), key=lambda kv: kv[1].start_ns):
        log(f"  {name} 1 {ms(s):.1f} parent={s.parent}")
    for name, s in sorted(w["first"].items()):
        log(f"  {name} of step 0: {ms(s):.1f}")
    # the wrapper's input_wait_ms and the program's span time the same call:
    # the same mean over the traced slice, from the two sides
    waits = ctx["samples"].get("waits") or []
    if waits:
        lo, hi = waits[0][0] * 1e9, waits[-1][0] * 1e9
        mine = [ms(s) for s in w["stages"]["train.input_wait"].values()
                if lo - 1e6 <= s.start_ns <= hi]
        if mine:
            log(f"  traced slice: train.input_wait mean {statistics.fmean(mine):.4f} ms "
                f"over {len(mine)}; the wrapper's bench_input_wait "
                f"{1e3 * statistics.fmean(d for _, d in waits):.4f} ms over {len(waits)}")
