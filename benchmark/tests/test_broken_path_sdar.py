"""`test_broken_path_lm.py` for the block-diffusion cell: the rest of a run
with the timed path broken underneath has to read `correct` false.
`runner.run` is called directly at the rehearsal's toy sizes on the CPU. Four
breaks of what this configuration adds: a noised block that reads its own
CLEAN block (`>=` where the noised -> clean rule has `>`: the answer leaks),
the noised stream at positions L..2L-1 instead of 0..L-1, the weight 1 / t
dropped from the loss, and the loss read against the NEXT token (the shift by
one of next-token training).

The same four breaks at the cell's own sizes, on the chip (what `limits_why`
quotes beside the sound readings; one run a break):

    python benchmark/tests/test_broken_path_sdar.py <seed> [out.jsonl]
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "sdar_bd4_8k"


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


def own_clean_block_step(trainer, monkeypatch):
    """noised i -> clean j allowed iff blk(i) >= blk(j): a noised block reads
    the clean tokens it is asked to predict. In the kernels' rule (the tile's
    lower bound on blk(i) - blk(j)) and in the dense op's mask alike; the
    step traces on its first call, with the patch."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("ddp_classification_pytorch_tpu.ops.flash_attention")
    dense = importlib.import_module("ddp_classification_pytorch_tpu.ops.attention")
    real_tile = fa._diffusion_tile

    def tile(bq, bk, jq, jk, diffusion):
        r0, c0, lo, hi = real_tile(bq, bk, jq, jk, diffusion)
        noised_q = (jq >= diffusion[0] // bq) * 1
        noised_k = (jk >= diffusion[0] // bk) * 1
        return r0, c0, lo - noised_q * (1 - noised_k), hi

    def mask(t, block):
        half = t // 2
        at = jnp.arange(t)
        noised, blk = at >= half, (at % half) // block
        d = blk[:, None] - blk[None, :]
        return jnp.where(noised[None, :], noised[:, None] & (d == 0), d >= 0)

    monkeypatch.setattr(fa, "_diffusion_tile", tile)
    monkeypatch.setattr(dense, "diffusion_mask", mask)


def running_positions_step(trainer, monkeypatch):
    """The rotary embedding at positions 0..2L-1 over the joined row: the
    noised copy of token i stands at L + i."""
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = decoder_lm._ROTARY["half"]
    monkeypatch.setitem(decoder_lm._ROTARY, "half",
                        lambda x, theta, streams=1: real(x, theta))


def unweighted_step(trainer, monkeypatch):
    """Every masked position weighs 1: the batch's levels are set to the
    grid's top (t = 1) on their way into the step."""
    real = trainer.train_step

    def step(state, tokens, targets):
        return real(state, tokens, targets.at[:, 1].set(65536))

    trainer.train_step = step


def shifted_loss_step(trainer, monkeypatch):
    """Position i scored against x_0[i + 1]: next-token training's shift."""
    import jax.numpy as jnp
    from ddp_classification_pytorch_tpu.ops import lm_head

    real = lm_head.blocked_cross_entropy
    monkeypatch.setattr(
        lm_head, "blocked_cross_entropy",
        lambda h, kernel, targets, *a, **k: real(h, kernel, jnp.roll(targets, -1), *a, **k))


FAULTS = (own_clean_block_step, running_positions_step, unweighted_step,
          shifted_loss_step)


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
    only = os.environ.get("FAULTS", "")     # e.g. FAULTS=unweighted_step
    for i, fault in enumerate(FAULTS):
        if only and fault.__name__ not in only.split(","):
            continue
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
