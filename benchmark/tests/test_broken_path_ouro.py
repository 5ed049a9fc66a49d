"""`test_broken_path_lm.py` for the looped cell: the rest of a run with the
timed path broken underneath has to read `correct` false. `runner.run` is
called directly at the rehearsal's toy sizes on the CPU. Two breaks of what
this configuration adds: a pass that restarts from the states BEFORE the
final norm (the norm then closes each pass for the head and the gate only),
and an exit distribution that carries no gradient (the passes' losses are
weighted by it, but neither the gate nor the states learn from the weights).

The same two breaks at the cell's own sizes, on the chip (what `limits_why`
quotes beside the sound readings; one run a break):

    python benchmark/tests/test_broken_path_ouro.py <seed> [out.jsonl]
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "ouro_loop4_8k"


def drive(monkeypatch, tamper=None, control="", seed=3000000007, rehearse=True,
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


def unnormed_restart_step(trainer, monkeypatch):
    """Pass t + 1 starts from the stack's output and not from its norm (the
    step traces on its first call, with the patched pass)."""
    from ddp_classification_pytorch_tpu.models import decoder_lm

    def one_pass(mdl, x, _):
        for layer in mdl.layers:
            x = layer(x)[0]
        return x, mdl.norm_final(x).astype(mdl.dtype)

    monkeypatch.setattr(decoder_lm, "_one_pass", one_pass)


def constant_weights_step(trainer, monkeypatch):
    """The exit distribution weighs the passes and nothing flows back
    through it."""
    import jax
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = decoder_lm.exit_distribution
    monkeypatch.setattr(decoder_lm, "exit_distribution",
                        lambda gate, states: jax.lax.stop_gradient(real(gate, states)))


FAULTS = (unnormed_restart_step, constant_weights_step)


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
                         seconds=float(os.environ.get("LIMITS_SECONDS", "2")))
        row = {"cell": CELL, "fault": fault.__name__, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "compared": r["compared"]}
        print("FAULT " + json.dumps(row), flush=True)
        if len(sys.argv) > 2:
            os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])), exist_ok=True)
            with open(sys.argv[2], "a") as f:
                f.write(json.dumps(row) + "\n")
