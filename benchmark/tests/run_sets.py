"""Two sets of runs of one cell with the same seeds in both, as the bound
rule asks, each run a process of its own like the driver's:

    python benchmark/tests/run_sets.py <cell> <runs_per_set> [out.jsonl [first_seed]]

With `out.jsonl`, every run's whole output (the step intervals among it) is
kept beside it as `out.jsonl.set<s>.seed<n>.log`.

Prints, per end-to-end metric and set, the median and the spread (distance
between the first and third quartile of statistics.quantiles(n=4) over the
median), and keeps every result line. Never imports JAX itself: a parent
that touched JAX would hold the chip."""

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    cell, n = sys.argv[1], int(sys.argv[2])
    out = sys.argv[3] if len(sys.argv) > 3 else ""
    first = int(sys.argv[4]) if len(sys.argv) > 4 else 2300000023
    seeds = [first + 104729 * i for i in range(n)]
    rows = []
    for s in (1, 2):
        for seed in seeds:
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", cell, "--seed", str(seed), "--trace", "0"],
                cwd=ROOT, capture_output=True, text=True)
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            if out:
                os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
                with open(f"{out}.set{s}.seed{seed}.log", "w") as f:
                    f.write(p.stdout + p.stderr)
            try:
                row = json.loads(last)
            except ValueError:
                print(f"RUN set {s} seed {seed} rc {p.returncode}: no result\n"
                      + p.stdout[-1500:] + p.stderr[-1500:], flush=True)
                continue
            row.update(set=s, seed=seed, rc=p.returncode)
            rows.append(row)
            print("RUN " + json.dumps({k: row[k] for k in
                                       ("set", "seed", "rc", "correct", "attempted",
                                        "failed", "metrics", "compared")}), flush=True)
            if out:
                with open(out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    names = list(rows[0]["metrics"]) if rows else []
    for name in names:
        for s in (1, 2):
            v = [r["metrics"][name]["value"] for r in rows if r["set"] == s]
            if len(v) >= 2:
                print(f"SETS {cell} {name} set {s}: n {len(v)} median "
                      f"{statistics.median(v):.6g} spread {spread(v):.4%} "
                      f"min {min(v):.6g} max {max(v):.6g}", flush=True)
    print(f"SETS {cell} correct in {sum(r['correct'] for r in rows)} of {len(rows)} runs")


if __name__ == "__main__":
    main()
