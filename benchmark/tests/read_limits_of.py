"""`read_limits.py` for any cell: the same two readings (the largest gap
sound runs of the program show over its seeds, the smallest the fp8 control
shows), through the runner the cell's configuration names and not
`runners/train.py` alone:

    python benchmark/tests/read_limits_of.py <cell> <n_seeds> <n_control_seeds> [out.jsonl]
"""

import importlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main() -> None:
    import run as bench_run
    from benchmark.tests import hooks

    cell, n, n_control = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    out = sys.argv[4] if len(sys.argv) > 4 else ""
    seconds = float(os.environ.get("LIMITS_SECONDS", "1"))
    rehearse = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    first_seed = int(os.environ.get("LIMITS_FIRST_SEED", "2000000011"))
    rows = []
    for i in range(n):
        seed = first_seed + 7919 * i
        ctx = bench_run.load_context(cell, seed=seed, seconds=seconds, trace=False,
                                     rehearse=rehearse, t0=time.perf_counter())
        runner = importlib.import_module(f"benchmark.runners.{ctx.config['runner']}")
        sound_compare = runner.compare
        fp8 = hooks.control(runner, "fp8") if i < n_control else None
        try:
            r = runner.run(ctx, bench_run.require_devices(ctx.cell["chips"], rehearse))
        finally:
            runner.compare = sound_compare
        row = {"cell": cell, "seed": seed, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "compared": r["compared"],
               "control": fp8 and {k: (v if math.isfinite(v) else None)
                                   for k, v in fp8.items()}}
        rows.append(row)
        print("LIMITS " + json.dumps(row), flush=True)
        if out:
            os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
    inf = float("inf")  # a number that was not finite came back as null
    for name in rows[0]["compared"]:
        sound = [inf if r["compared"][name] is None else r["compared"][name]
                 for r in rows]
        control = [inf if r["control"][name] is None else r["control"][name]
                   for r in rows if r["control"]]
        line = f"SUMMARY {cell} {name}: program max {max(sound):.6g} min {min(sound):.6g}"
        if control:
            line += (f"; control min {min(control):.6g} max {max(control):.6g}; "
                     f"control min / program max = {min(control) / max(sound):.3g}")
        print(line, flush=True)


if __name__ == "__main__":
    main()
