"""The reduction from trace intervals to numbers: the union arithmetic on
hand-made intervals, and the whole reduction on a small recorded trace (the
intervals the reduction reads, from a traced chip run of PR 25, cut to a
few steps, times rounded to whole ns, gzipped: benchmark/tests/data/)."""

import glob
import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [(0, 4), (5, 7), (10, 11)]
    assert tr.total(tr.union([(0, 10), (2, 3), (9, 12)])) == 12
    assert tr.union([]) == []


def test_gaps_are_the_complement_inside_the_window():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, (0, 10)) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps(busy, (3, 6)) == [(4, 6)]
    assert tr.gaps([], (1, 2)) == [(1, 2)]
    assert tr.total(tr.gaps(busy, (0, 10))) + tr.total(busy) == 10


def handmade(devices=1):
    """Four steps of 100 ns: ops busy 80 ns of each (two overlapping ops and
    an all-reduce), the first and the last step cut off by the trace."""
    dev = {"ops": [], "modules": []}
    for k in range(4):
        t = 1000 + 100 * k
        dev["modules"].append(["jit_step(1)", t, 80])
        dev["ops"] += [["fusion.1", t, 50], ["fusion.2", t + 40, 20],
                       ["all-reduce.3", t + 60, 20]]
    dev["modules"].append(["jit_add(2)", 1085, 2])
    host = [["bench_input_wait", 1180, 15], ["bench_step_dispatch", 1195, 10],
            ["bench_input_wait", 1281, 18]]
    return {"devices": {str(i): json.loads(json.dumps(dev)) for i in range(devices)},
            "host": host}


def test_reduce_on_handmade_steps():
    r = tr.reduce(handmade(), chips=1)
    # the two whole steps in the middle: window from step 1's start to step 3's
    assert r["steps"] == 2 and r["step_module"] == "jit_step(1)"
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(160e-9)
    assert r["step_device_ms"] == pytest.approx(80e-6)
    assert r["idle_pct"] == pytest.approx(20.0)
    assert r["collective_ms"] == pytest.approx(20e-6)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(100e-9)
    assert list(ops)[0] == "fusion.1" and len(ops) == 3
    gaps = dict(r["breakdown"]["idle_gaps"])
    # the 20 ns gaps at 1180-1200 and 1280-1300: midpoints 1190, 1290
    assert gaps == {"bench_input_wait": pytest.approx(40e-9)}


def test_reduce_averages_busy_over_the_chips_used_and_needs_device_ops():
    four = handmade(devices=4)
    four["devices"]["3"]["ops"] = [op for op in four["devices"]["3"]["ops"]
                                   if not op[0].startswith("all-reduce")]
    r = tr.reduce(four, chips=4)
    assert r["busy_s"] == pytest.approx((3 * 160e-9 + 120e-9) / 4)
    assert tr.reduce({"devices": {}, "host": []}, chips=1) is None
    assert tr.reduce({"devices": {"0": {"ops": [], "modules": []}}, "host": []}) is None


def test_host_spans_move_onto_the_trace_clock_by_the_marker():
    t = handmade()
    t["host"] = []
    # the marker ended at 1090 ns on the trace; the host saw that at 5.00000009 s,
    # a second one at 1390 ns / 5.0000004 s (seen 10 ns late: the earlier wins)
    t["devices"]["0"]["modules"] += [["jit_bench_marker(9)", 1088, 2],
                                     ["jit_bench_marker(9)", 1388, 2]]
    spans = {"bench_input_wait": [(5.00000018, 15e-9), (4.0, 1e-9)],
             "bench_step_dispatch": [(5.000000195, 10e-9)]}
    host = tr.host_on_trace_clock(t, spans, [5.00000009, 5.0000004])
    assert [(n, round(s), round(d)) for n, s, d in host] == [
        ("bench_input_wait", 1180, 15), ("bench_step_dispatch", 1195, 10)]
    t["host"] = host
    r = tr.reduce(t)
    assert r["step_module"] == "jit_step(1)"  # the marker is never the step
    assert dict(r["breakdown"]["idle_gaps"])["bench_input_wait"] == pytest.approx(20e-9)
    assert tr.host_on_trace_clock(t, spans, []) == []


def test_async_collective_pairs_count_once():
    t = handmade()
    t["devices"]["0"]["ops"] += [["all-gather-start.7", 1110, 5],
                                 ["all-gather-done.7", 1150, 10]]
    assert tr.reduce(t)["collective_ms"] == pytest.approx((40 + 10) * 1e-6 / 2)


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "intervals_*.json.gz"))),
                         ids=os.path.basename)
def test_reduce_on_the_recorded_trace(path):
    assert os.path.getsize(path) < 200 * 1024
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    chips = len(recorded["devices"])
    r = tr.reduce(recorded, chips=chips)
    with open(path.replace("intervals_", "expected_")[:-3]) as f:
        expected = json.load(f)
    for key in ("steps", "step_module"):
        assert r[key] == expected[key]
    for key in ("busy_s", "window_s", "step_device_ms", "collective_ms", "idle_pct"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9, abs=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert [n for n, _ in r["breakdown"]["device_ops"]] == \
        [n for n, _ in expected["breakdown"]["device_ops"]]
    # the cut keeps whole steps: cutting again changes nothing
    assert tr.cut(recorded, steps=10 ** 6) == recorded


def test_a_recorded_trace_is_checked_in():
    assert glob.glob(os.path.join(DATA, "intervals_*.json.gz"))
