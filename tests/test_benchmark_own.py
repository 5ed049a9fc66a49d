"""Tier-1 runs the cheap half of the benchmark's own tests (`benchmark/tests`,
which this suite does not collect): the trace reducer, the readers of the
program's spans on one in-process rehearsal, and the static checks of
`BENCHMARK.json`, `run.py` and the analytic FLOPs. A span renamed in the
program, or `spans.snapshot()` reshaped, fails here and not as a `null` in
the ledger. The references, the subprocess rehearsals and the broken-path
controls cost minutes and stay in `benchmark/tests` (ROADMAP D13).

pytest collects a test from the namespace it finds it in, so importing the
names is the whole mechanism; the modules find their data by their own
`__file__`."""

import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "benchmark", "tests"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
pytest.register_assert_rewrite("test_trace_reduce", "test_program_spans",
                               "test_rehearsal")

# every test of these two (and the module-scoped `rehearsal` fixture)
from test_program_spans import *  # noqa: E402,F401,F403
from test_trace_reduce import *  # noqa: E402,F401,F403
# of this one only the static cases: the rest start a process per cell
from test_rehearsal import (  # noqa: E402,F401
    test_analytic_flops_match_the_published_counts,
    test_benchmark_json_names_units_and_moves,
    test_run_py_names_no_cell_config_mix_or_metric,
)


def _token_cells():
    import json

    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    files = {c["name"]: c["file"] for c in spec["configs"]}
    out = []
    for w in spec["workloads"]:
        with open(os.path.join(_ROOT, files[w["config"]])) as f:
            conf = json.load(f)
        if conf["runner"] == "train_lm":
            out.append((w["name"], spec, conf))
    return out


_TOKEN_CELLS = _token_cells()


@pytest.mark.parametrize("cell,spec,conf", _TOKEN_CELLS,
                         ids=[c[0] for c in _TOKEN_CELLS])
def test_token_cell_has_its_reference_its_counts_and_its_readers(cell, spec, conf):
    """Every decoder cell (`st21b_ep4_8k`, `joyai_ep16_8k`, `lfm2_ep4_8k`,
    `ling3_ep64_8k`, `ouro_loop4_8k`, `olmoh_tp2_8k`, `sdar_bd4_8k`): what the runner and the readers import by the
    configuration's names is there, the file's parameter count is the reference's own, and the
    rehearsal is held to the same numbers as the chip run."""
    import importlib

    import numpy as np

    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    flops = importlib.import_module(f"benchmark.flops.{conf['flops']}")
    arch = conf["arch"]
    assert sum(int(np.prod(s[0])) for s in ref.param_spec(arch).values()) \
        == conf["parameters"]
    assert callable(ref.loss_for(arch)) and callable(ref.loss_for(arch, "fp8"))
    assert flops.train_flops_per_image(arch, 0) > 0
    assert set(conf["limits"]) == set(conf["rehearse"]["limits"])
    assert all(0 < v < 1 for v in conf["limits"].values())
    assert set(conf["rehearse"]["arch"]) == set(arch)
    # the readers the cell lists (data: a decoder without routed experts or
    # without attention lists fewer), and the counts that each share divides by
    mine = [m["name"] for m in spec["per_layer"] if cell in m.get("workloads", [cell])]
    assert "mfu_pct" in mine
    for name in mine:
        assert callable(importlib.import_module(f"benchmark.layers.{name}").read)
    rows = conf["batch_per_chip"]
    if "attn_roofline_pct" in mine:
        assert flops.train_flops_per_image(arch, 0) > flops.attention_flops(arch, rows) / rows > 0
    if "moe_gmm_roofline_pct" in mine:
        assert flops.gmm_flops(1.0, arch) == 6.0 * 3 * arch["hidden_size"] * arch["expert_width"]
    # a published key the file lists as reduced differs from `published`
    for key in conf["reduced"]:
        assert conf[key] != conf["published"][key], key
