"""expert layer: how uneven the routing left the held experts — the
token-slots of the busiest held expert over the mean of the held experts,
in the worst layer of a step, mean over the window's steps. From the step's
own `moe_load` counters (its metrics), which the runner keeps per step. 1.0
is perfectly even; the grouped matmuls' tiles and a real exchange's buffers
are sized by the busiest."""

import numpy as np


def read(ctx):
    loads = ctx["samples"].get("moe_load") or []
    if not loads:
        return None
    ratio = np.stack([x.max(axis=1) / np.maximum(x.mean(axis=1), 1e-9) for x in loads])
    held = np.mean([x.sum(axis=1) for x in loads], axis=0)
    print("[bench] moe_load over the window's steps, by layer: busiest / mean "
          + " ".join(f"{v:.3f}" for v in ratio.mean(axis=0))
          + "; token-slots on held experts " + " ".join(f"{v:.0f}" for v in held),
          flush=True)
    return float(np.mean(ratio.max(axis=1)))
