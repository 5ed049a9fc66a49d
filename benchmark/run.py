"""One process, one cell, one run:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, its configuration from
benchmark/configs/<config>.json and its traffic mix from
benchmark/traffic/<traffic>.json, imports the runner the configuration
names (benchmark/runners/<runner>.py) and prints the result as the last
line. This file names no cell, configuration, mix or metric.

No accelerator (or fewer chips than the cell asks for) => exit 3 and no
result line. `--rehearse` is the CPU rehearsal (tiny shapes from the
configuration's "rehearse" block, platform "cpu" in the result, numbers
that are counts of what ran and never device numbers).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as a script can take it

import argparse
import dataclasses
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class RunContext:
    cell: dict
    config: dict
    traffic: dict
    spec: dict            # BENCHMARK.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t0: float
    cache_dir: str        # benchmark/.cache: datasets, traces, run dirs


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_context(workload: str, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t0: float = T0) -> RunContext:
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    return RunContext(cell=cell, config=config, traffic=traffic, spec=spec,
                      seed=seed, seconds=seconds, trace=trace,
                      rehearse=rehearse, t0=t0,
                      cache_dir=os.path.join(BENCH_DIR, ".cache"))


def require_devices(chips: int, rehearse: bool):
    """The devices this cell runs on, or exit 3: a measurement that finds
    no chip fails, it does not fall back to the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not rehearse:
        print("no accelerator found (JAX platform is cpu); --rehearse is the "
              "CPU rehearsal", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"the cell asks for {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny shapes (JAX_PLATFORMS=cpu)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)  # the package under test lives beside benchmark/
    ctx = load_context(args.workload, args.seed, 0.0, bool(args.trace),
                       args.rehearse)
    ctx.seconds = float(ctx.spec["run_seconds"] if args.seconds is None
                        else args.seconds)
    if args.rehearse:
        # pin the platform before JAX initialises, and give the rehearsal
        # the virtual devices a several-chip cell needs
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={ctx.cell['chips']}")
    devices = require_devices(ctx.cell["chips"], args.rehearse)
    runner = importlib.import_module(f"benchmark.runners.{ctx.config['runner']}")
    result = runner.run(ctx, devices)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
