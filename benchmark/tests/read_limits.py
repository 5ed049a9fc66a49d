"""Read, on the chip, the two numbers every limit is set from: the largest
gap sound runs of the program show over a dozen seeds, and the smallest the
lower-precision control shows. One process per cell (set-up is long):

    python benchmark/tests/read_limits.py <cell> <n_seeds> <n_control_seeds> [out.jsonl]

Each seed is one short run of the ordinary runner (the window's own call
and feed); the control is the plain reference computed with fp8 operands,
held against the float32 reference on the same batches.
"""

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

    cell, n, n_control = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    out = sys.argv[4] if len(sys.argv) > 4 else ""
    seconds = float(os.environ.get("LIMITS_SECONDS", "1"))
    rehearse = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    from benchmark.runners import train as runner
    from benchmark.tests import hooks

    sound_compare = runner.compare
    rows = []
    for i in range(n):
        seed = 2000000011 + 7919 * i
        ctx = bench_run.load_context(cell, seed=seed, seconds=seconds, trace=False,
                                     rehearse=rehearse, t0=time.perf_counter())
        runner.compare = sound_compare
        fp8 = hooks.control(runner, "fp8") if i < n_control else None
        r = runner.run(ctx, bench_run.require_devices(ctx.cell["chips"], rehearse))
        row = {"cell": cell, "seed": seed, "correct": r["correct"],
               "compared": r["compared"],
               "control": fp8 and {k: (v if math.isfinite(v) else None)
                                   for k, v in fp8.items()}}
        rows.append(row)
        print("LIMITS " + json.dumps(row), flush=True)
        if out:
            os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
    for name in rows[0]["compared"]:
        inf = float("inf")  # a number that was not finite came back as null
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
