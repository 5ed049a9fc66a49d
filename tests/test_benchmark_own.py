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
