"""What a builder or a test hangs on the runner from outside; the measured
entry point (`run.py`, `runners/train.py`) carries none of it.

    tamper(runner, fn)           break the timed path: fn(trainer) once it is built
    control(runner, precision)   also follow the lower-precision control;
                                 -> the dict its numbers land in
    keep_intervals(path)         write the intervals a traced run's reduction
                                 read, cut to a few steps (a test fixture)

    python benchmark/tests/hooks.py keep-intervals <out.json> <run.py's arguments>
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tamper(runner, fn) -> None:
    real = runner.build_trainer

    def build_trainer(ctx, devices):
        built = real(ctx, devices)
        fn(built[0])
        return built

    runner.build_trainer = build_trainer


def control(runner, precision: str) -> dict:
    """The control is the plain reference put in the program's place and
    computed in `precision`, held against the float32 reference on the
    same batches as the program is."""
    numbers: dict = {}

    def compare(ctx, ref, arch, mesh, batches, got):
        want = runner.follow(ctx, ref, arch, mesh, batches, "float32")
        numbers.clear()
        numbers.update(runner.gaps(
            f"control({precision})",
            runner.follow(ctx, ref, arch, mesh, batches, precision), want, mesh))
        return runner.gaps("program", got, want, mesh)

    runner.compare = compare
    return numbers


def keep_intervals(path: str) -> None:
    from benchmark import trace_reduce

    real = trace_reduce.load_dir

    def load_dir(*args, **kwargs):
        intervals = real(*args, **kwargs)
        if intervals is not None and intervals["devices"]:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(path, "w") as f:
                json.dump(trace_reduce.cut(intervals), f)
        return intervals

    trace_reduce.load_dir = load_dir


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as bench_run

    assert sys.argv[1] == "keep-intervals", __doc__
    keep_intervals(sys.argv[2])
    bench_run.main(sys.argv[3:])
