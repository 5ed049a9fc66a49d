"""`test_broken_path_lm.py` for the second token cell: the rest of a run with
the timed path broken underneath has to read `correct` false. `runner.run` is
called directly at the rehearsal's toy sizes on the CPU. Two breaks of what
this configuration adds: a router that chooses by its scores alone (the
selection bias ignored), and a step whose loss leaves the prediction module's
term out."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "joyai_ep16_8k"


def drive(monkeypatch, tamper=None, control="", seed=3000000007):
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
    ctx = bench_run.load_context(CELL, seed=seed, seconds=1.0, trace=False,
                                 rehearse=True, t0=time.perf_counter())
    return runner.run(ctx, jax.devices()[:ctx.cell["chips"]]), lower


def unbiased_choice_step(trainer, monkeypatch):
    """The router chooses the top-k of its scores; `b` is never read (the
    step traces on its first call, with the patched router)."""
    from ddp_classification_pytorch_tpu.ops import moe

    real = moe.route_top_k
    monkeypatch.setattr(
        moe, "route_top_k",
        lambda logits, top_k, **route: real(logits, top_k, **dict(route, bias=None)))


def no_prediction_loss_step(trainer, monkeypatch):
    """The step's loss is the main path's alone."""
    from ddp_classification_pytorch_tpu.models.factory import build_model
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    cfg = copy.deepcopy(trainer.cfg)
    cfg.model.decoder.mtp_weight = 0.0
    model = build_model(cfg.model, cfg.data.num_classes, mesh=trainer.mesh)
    trainer.train_step = make_train_step(cfg, model, trainer.tx, mesh=trainer.mesh)


def test_sound_run_is_correct_and_the_control_lies_further(monkeypatch):
    r, lower = drive(monkeypatch, control="fp8")
    assert r["correct"] is True
    assert any(lower[k] > 1.5 * r["compared"][k] for k in r["compared"])


@pytest.mark.parametrize("tamper", [unbiased_choice_step, no_prediction_loss_step],
                         ids=lambda f: f.__name__)
def test_broken_step_is_not_correct(tamper, monkeypatch):
    r, _ = drive(monkeypatch, tamper=tamper)
    assert r["correct"] is False
    assert r["attempted"] > 0  # the run itself went through
