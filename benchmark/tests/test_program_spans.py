"""The readers of the program's own spans (benchmark/layers/_program_spans.py
and the seven metrics over it), on one traced CPU rehearsal run in this
process: each gives a float where the program recorded, None where it did
not; the program's spans and the runner's wrappers are on one clock."""

import importlib
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

NEW = ["input_wait_p90_ms", "input_load_ms", "input_assemble_ms", "input_starved_pct",
       "step_dispatch_ms", "init_state_s", "first_dispatch_s"]
CELL = "rn50_folder"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    UNITS = {m["name"]: m["unit"] for m in json.load(f)["per_layer"]}


@pytest.fixture(scope="module")
def rehearsal():
    """-> (result line, the runner's _Run, the context the readers were given)"""
    import jax
    import run as bench_run  # benchmark/run.py
    from benchmark.layers import input_wait_ms
    from benchmark.runners import train as runner

    kept = {}

    class Run(runner._Run):
        def __init__(self, *args):
            super().__init__(*args)
            kept["run"] = self

    real_read = input_wait_ms.read

    def read(ctx):
        kept["ctx"] = ctx
        return real_read(ctx)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runner, "_Run", Run)
        mp.setattr(input_wait_ms, "read", read)
        ctx = bench_run.load_context(CELL, seed=3000000011, seconds=1.5, trace=True,
                                     rehearse=True, t0=time.perf_counter())
        result = runner.run(ctx, jax.devices()[:ctx.cell["chips"]])
    return result, kept["run"], kept["ctx"]


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_a_float_on_the_rehearsal(rehearsal, name):
    result, _, ctx = rehearsal
    assert result["correct"] is True
    got = result["metrics"][name]
    assert isinstance(got["value"], float) and got["unit"] == UNITS[name]
    assert got["value"] >= 0.0
    if name == "input_starved_pct":
        assert got["value"] <= 100.0
    # and the reader gives the same again from the context it was handed
    reader = importlib.import_module(f"benchmark.layers.{name}")
    assert reader.read(ctx) == got["value"]


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_where_the_program_recorded_nothing(name, monkeypatch, capsys):
    from ddp_classification_pytorch_tpu import obs
    from ddp_classification_pytorch_tpu.obs import spans

    reader = importlib.import_module(f"benchmark.layers.{name}")
    ctx = {"config": {"warmup_steps": 8}, "samples": {"waits": []}}
    monkeypatch.setattr(spans, "snapshot", lambda: [])
    assert reader.read(dict(ctx)) is None
    # a program older than its recorder: the import fails, nothing raises
    monkeypatch.delattr(obs, "spans")
    monkeypatch.setitem(sys.modules, "ddp_classification_pytorch_tpu.obs.spans", None)
    assert reader.read(dict(ctx)) is None
    assert "program spans" not in capsys.readouterr().out


def test_the_table_is_logged_once_and_names_every_span(rehearsal, capsys):
    from benchmark.layers import _program_spans as ps

    _, _, ctx = rehearsal
    fresh = {k: v for k, v in ctx.items() if k != ps._KEY}
    for name in NEW:
        importlib.import_module(f"benchmark.layers.{name}").read(fresh)
    out = capsys.readouterr().out
    assert out.count("[bench] program spans") == 1
    for name in ps.STAGES + ("train.epoch self/step", "train.epoch 1", "setup.trainer",
                             "setup.mesh", "setup.loaders", "setup.init_state",
                             "setup.build_steps", "setup.checkpoint", "traced slice"):
        assert f"  {name}" in out, name
    assert "setup.datasets" not in out  # the runner hands its datasets in


def test_program_span_and_wrapper_time_the_same_call_on_one_clock(rehearsal):
    from ddp_classification_pytorch_tpu.obs import spans

    _, run, _ = rehearsal
    epoch = [s for s in spans.snapshot() if s.name == "train.epoch"][-1]
    mine = sorted((s for s in spans.snapshot()
                   if s.name == "train.input_wait" and s.start_ns >= epoch.start_ns),
                  key=lambda s: s.ids["step"])
    # the wrapper's k-th next() is step k's; its last one may have ended the epoch
    assert len(mine) >= 8 and len(mine) <= len(run.waits) <= len(mine) + 1
    for s, (t, d) in zip(mine, run.waits):
        # the program's span is around the wrapper's: inside it to within 1 ms
        assert s.start_ns <= t * 1e9 + 1 and (t + d) * 1e9 <= s.end_ns + 1
        assert t * 1e9 - s.start_ns < 1e6 and s.end_ns - (t + d) * 1e9 < 1e6
    disp = sorted((s for s in spans.snapshot()
                   if s.name == "train.step_dispatch" and s.start_ns >= epoch.start_ns),
                  key=lambda s: s.ids["step"])
    assert len(disp) == len(run.dispatches)
    for s, (t, d) in zip(disp, run.dispatches):
        assert s.start_ns <= t * 1e9 + 1 and (t + d) * 1e9 <= s.end_ns + 1


def test_host_on_trace_clock_takes_the_snapshot_reshaped(rehearsal):
    from benchmark import trace_reduce as tr
    from ddp_classification_pytorch_tpu.obs import spans

    epoch = [s for s in spans.snapshot() if s.name == "train.epoch"][-1]
    kept = [s for s in spans.snapshot()
            if epoch.start_ns <= s.start_ns and s.end_ns <= epoch.end_ns
            and s.name in ("input.load", "input.assemble", "train.input_wait",
                           "train.step_dispatch")]
    host_spans = {}
    for s in kept:  # the form the runner hands over: name -> (start s, seconds)
        host_spans.setdefault(s.name, []).append(
            (s.start_ns * 1e-9, (s.end_ns - s.start_ns) * 1e-9))
    assert len(host_spans) == 4
    # a trace whose marker ended at 1000 ns when the host read epoch.start
    length = epoch.end_ns - epoch.start_ns
    intervals = {"devices": {"0": {
        "ops": [["fusion.1", 0, length + 2000]],
        "modules": [["jit_bench_marker(1)", 990, 10]]}}, "host": []}
    rows = tr.host_on_trace_clock(intervals, host_spans, [epoch.start_ns * 1e-9])
    assert len(rows) == len(kept)
    assert [r[1] for r in rows] == sorted(r[1] for r in rows)
    want = sorted((s.start_ns - epoch.start_ns + 1000, s.name, s.end_ns - s.start_ns)
                  for s in kept)
    for (name, start, dur), (w_start, w_name, w_dur) in zip(rows, want):
        assert name == w_name
        assert start == pytest.approx(w_start, abs=64) and dur == pytest.approx(w_dur, abs=64)
