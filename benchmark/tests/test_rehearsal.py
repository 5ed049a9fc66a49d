"""`run.py --rehearse` on the CPU for every cell of BENCHMARK.json, the
refusal to run without a chip, BENCHMARK.json's own names and units, and
the analytic FLOP counts against the published ones."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def run_cell(cell, *extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "2", *extra],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    p = run_cell(cell, "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["attempted"] > 0 and last["failed"] == 0
    chips = {w["name"]: w["chips"] for w in SPEC["workloads"]}[cell]
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == chips
    assert "memory_peak_bytes" in last["device"]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in SPEC[kind]
               if cell in m.get("workloads", [cell])}
    assert last["metrics"], "a result line with no metric"
    for name, m in last["metrics"].items():
        assert m["unit"] == allowed[name] and isinstance(m["value"], float)
    if not trace:
        assert set(last["metrics"]) == set(allowed)
    # every number compared is printed beside its limit
    assert len(re.findall(r"compared \w+ = .* limit ", p.stdout)) >= 5


def test_no_chip_means_no_result():
    p = run_cell(CELLS[0], "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{") and '"correct"' not in p.stdout


def test_benchmark_json_names_units_and_moves():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    every = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in every]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[group]}) == len(SPEC[group])
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(CELLS) // 4)
    for c in SPEC["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS and cell in moved.get("workloads", CELLS)
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_run_py_names_no_cell_config_mix_or_metric():
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        text = f.read()
    traffic = {w["traffic"] for w in SPEC["workloads"]}
    for name in set(CELLS) | {c["name"] for c in SPEC["configs"]} | traffic | \
            {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}:
        assert not re.search(rf"\b{re.escape(name)}\b", text), name


@pytest.mark.parametrize("module,arch,published_gmac", [
    ("resnet", {"block": "bottleneck", "stage_sizes": [3, 4, 6, 3], "num_filters": 64,
                "stem": "imagenet", "num_classes": 1000}, 4.1),
    ("vit", {"patch": 16, "dim": 768, "depth": 12, "heads": 12, "image_size": 224,
             "num_classes": 1000}, 17.5),
])
def test_analytic_flops_match_the_published_counts(module, arch, published_gmac):
    import importlib

    sys.path.insert(0, ROOT)
    flops = importlib.import_module(f"benchmark.flops.{module}")
    gmac = flops.forward_macs(arch, 224) / 1e9
    assert abs(gmac - published_gmac) / published_gmac < 0.02
    assert flops.train_flops_per_image(arch, 224) == 6.0 * flops.forward_macs(arch, 224)
    # the configuration files carry the same shapes
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        if conf["flops"] == module:
            assert conf["arch"] == arch
