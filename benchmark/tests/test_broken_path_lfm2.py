"""`test_broken_path_lm.py` for the third token cell: the rest of a run with
the timed path broken underneath has to read `correct` false. `runner.run` is
called directly at the rehearsal's toy sizes on the CPU. Two breaks of what
this configuration adds: a short convolution whose output gate C is dropped
(a = c W_out), and a head whose gradient does not reach the table (what an
untied head leaves the embedding with: the lookup's part alone).

The same two breaks at the cell's own sizes, on the chip (what `limits_why`
quotes beside the sound readings; one run a break, about two minutes each):

    python benchmark/tests/test_broken_path_lfm2.py <seed> [out.jsonl]
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "lfm2_ep4_8k"


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


def ungated_operator_step(trainer, monkeypatch):
    """The operator's last gate is dropped: a = c W_out, not (C * c) W_out
    (the step traces on its first call, with the patched split)."""
    import jax.numpy as jnp
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = jnp.split

    def split(x, parts, axis=0):
        out = real(x, parts, axis=axis)
        if parts == 3:      # [B | C | X]: the C gate reads 1
            out = [out[0], jnp.ones_like(out[1]), out[2]]
        return out

    class Patched:
        def __getattr__(self, name):
            return split if name == "split" else getattr(jnp, name)

    monkeypatch.setattr(decoder_lm, "jnp", Patched())


def untied_gradient_step(trainer, monkeypatch):
    """The head reads the table, and its gradient stops there."""
    import jax
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = decoder_lm.head_kernel
    monkeypatch.setattr(decoder_lm, "head_kernel",
                        lambda params, cfg: jax.lax.stop_gradient(real(params, cfg)))


def test_sound_run_is_correct_and_the_control_lies_further(monkeypatch):
    r, lower = drive(monkeypatch, control="fp8")
    assert r["correct"] is True
    assert any(lower[k] > 1.5 * r["compared"][k] for k in r["compared"])


@pytest.mark.parametrize("tamper", [ungated_operator_step, untied_gradient_step],
                         ids=lambda f: f.__name__)
def test_broken_step_is_not_correct(tamper, monkeypatch):
    r, _ = drive(monkeypatch, tamper=tamper)
    assert r["correct"] is False
    assert r["attempted"] > 0  # the run itself went through


if __name__ == "__main__":
    import json

    sys.path.insert(0, ROOT)
    for i, fault in enumerate((ungated_operator_step, untied_gradient_step)):
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
