"""`test_broken_path_lm.py` for the fourth token cell: the rest of a run with
the timed path broken underneath has to read `correct` false. `runner.run` is
called directly at the rehearsal's toy sizes on the CPU. Two breaks of what
this configuration adds: a KDA step without its decay (the state forgets
nothing: g = 0 on every channel), and a router without its group limit (the
top-k over all the experts, as the configurations before this one choose).

The same two breaks at the cell's own sizes, on the chip (what `limits_why`
quotes beside the sound readings; one run a break):

    python benchmark/tests/test_broken_path_ling.py <seed> [out.jsonl]
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "ling3_ep64_8k"


def drive(monkeypatch, tamper=None, control="", seed=3000007926, rehearse=True,
          seconds=1.0):
    import time

    import jax
    import run as bench_run  # benchmark/run.py
    from benchmark.runners import train_lm as runner
    from benchmark.tests import hooks

    monkeypatch.setattr(runner, "build_trainer", runner.build_trainer)
    monkeypatch.setattr(runner, "compare", runner.compare)
    if tamper is not None:
        hooks.tamper(runner, lambda trainer: tamper(trainer, monkeypatch))
    lower = hooks.control(runner, control) if control else None
    ctx = bench_run.load_context(CELL, seed=seed, seconds=seconds, trace=False,
                                 rehearse=rehearse, t0=time.perf_counter())
    return runner.run(ctx, jax.devices()[:ctx.cell["chips"]]), lower


def undecayed_state_step(trainer, monkeypatch):
    """The recurrence runs with g = 0: S_t = (I - beta k k^T) S_{t-1} + beta k
    v^T (the step traces on its first call, with the patched op)."""
    import jax.numpy as jnp
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = decoder_lm.kda_chunked
    monkeypatch.setattr(
        decoder_lm, "kda_chunked",
        lambda q, k, v, g, beta, **kw: real(q, k, v, jnp.zeros_like(g), beta, **kw))


def ungrouped_router_step(trainer, monkeypatch):
    """The top-k is taken over all the experts: no group is dropped."""
    from ddp_classification_pytorch_tpu.ops import moe

    real = moe.route_top_k

    def route(logits, top_k, **kw):
        assert kw.pop("n_group") > 1 and kw.pop("topk_group")
        return real(logits, top_k, **kw)

    monkeypatch.setattr(moe, "route_top_k", route)


FAULTS = (undecayed_state_step, ungrouped_router_step)


def test_sound_run_is_correct_and_the_control_lies_further(monkeypatch):
    r, lower = drive(monkeypatch, control="fp8")
    assert r["correct"] is True
    assert any(lower[k] > 1.5 * r["compared"][k] for k in r["compared"])


@pytest.mark.parametrize("tamper", FAULTS, ids=lambda f: f.__name__)
def test_broken_step_is_not_correct(tamper, monkeypatch):
    r, _ = drive(monkeypatch, tamper=tamper)
    assert r["correct"] is False
    assert r["attempted"] > 0  # the run itself went through


if __name__ == "__main__":
    import json

    sys.path.insert(0, ROOT)
    for i, fault in enumerate(FAULTS):
        with pytest.MonkeyPatch.context() as mp:
            r, _ = drive(mp, tamper=fault, seed=int(sys.argv[1]) + 7919 * i,
                         rehearse=os.environ.get("JAX_PLATFORMS", "") == "cpu",
                         seconds=float(os.environ.get("LIMITS_SECONDS", "3")))
        row = {"cell": CELL, "fault": fault.__name__, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "compared": r["compared"]}
        print("FAULT " + json.dumps(row), flush=True)
        if len(sys.argv) > 2:
            os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])), exist_ok=True)
            with open(sys.argv[2], "a") as f:
                f.write(json.dumps(row) + "\n")
