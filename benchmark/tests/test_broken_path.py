"""The rest of a run with the timed path broken underneath: `correct` has to
come out false. Skips the harness's look for a chip (`runner.run` is called
directly, at the rehearsal's toy sizes on the CPU), and also shows that the
lower-precision control lies further from the reference than the program."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def drive(cell, monkeypatch, tamper=None, control=""):
    """-> (the run's result, the control's numbers or None)"""
    import time

    import jax
    import run as bench_run  # benchmark/run.py
    from benchmark.runners import train as runner
    from benchmark.tests import hooks

    monkeypatch.setattr(runner, "build_trainer", runner.build_trainer)
    monkeypatch.setattr(runner, "compare", runner.compare)
    if tamper is not None:
        hooks.tamper(runner, tamper)
    lower = hooks.control(runner, control) if control else None
    ctx = bench_run.load_context(cell, seed=3000000007, seconds=1.0, trace=False,
                                 rehearse=True, t0=time.perf_counter())
    return runner.run(ctx, jax.devices()[:ctx.cell["chips"]]), lower


def identity_step(trainer):
    """A step that returns its state unchanged (the counter apart)."""
    import jax
    import jax.numpy as jnp

    real = trainer.train_step

    def step(state, images, labels):
        old = jax.tree_util.tree_map(jnp.copy, (state.params, state.opt_state))
        new, metrics = real(state, images, labels)
        return new.replace(params=old[0], opt_state=old[1]), metrics

    trainer.train_step = step


def half_batch_step(trainer):
    """A step that leaves out a part of the batch: the second half of the
    rows is replaced by the first."""
    import jax.numpy as jnp

    real = trainer.train_step

    def step(state, images, labels):
        h = images.shape[0] // 2
        return real(state, jnp.concatenate([images[:h], images[:h]]),
                    jnp.concatenate([labels[:h], labels[:h]]))

    trainer.train_step = step


CELL = "rn50_folder"


def test_sound_run_is_correct_and_the_control_lies_further(monkeypatch):
    r, lower = drive(CELL, monkeypatch, control="fp8")
    assert r["correct"] is True
    assert any(lower[k] > 1.5 * r["compared"][k] for k in r["compared"])


@pytest.mark.parametrize("tamper", [identity_step, half_batch_step],
                         ids=lambda f: f.__name__)
def test_broken_step_is_not_correct(tamper, monkeypatch):
    r, _ = drive(CELL, monkeypatch, tamper=tamper)
    assert r["correct"] is False
    assert r["attempted"] > 0  # the run itself went through
