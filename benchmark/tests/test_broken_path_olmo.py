"""`test_broken_path_lm.py` for the hybrid cell: the rest of a run with the
timed path broken underneath has to read `correct` false. `runner.run` is
called directly at the rehearsal's toy sizes on the CPU. Four breaks of what
this configuration adds or states: parameters kept in bfloat16 (the
configuration states float32 parameters under bf16 compute), a beta without
its factor of 2 (the delta rule of the models before `linear_allow_neg_eigval`),
a query without its d_k^-1/2, and a block that norms its sub-layers' INPUTS
with the leaves that should norm their outputs (the usual pre-norm block).

The same four breaks at the cell's own sizes, on the chip (what `limits_why`
quotes beside the sound readings; one run a break):

    python benchmark/tests/test_broken_path_olmo.py <seed> [out.jsonl]
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "olmoh_tp2_8k"


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


def bf16_parameters_step(trainer, monkeypatch):
    """Every parameter is rounded to bfloat16 before and after each step: a
    warm-up update of 1e-6 is lost in a bfloat16 weight. `reduce_precision`
    and not a cast there and back, which the TPU's compiler takes out as
    excess precision it may keep (the cast pair read `correct` true on the
    chip, every gap a sound run's: chiprun_out/pr49_a/faults.jsonl)."""
    import jax

    real = trainer.train_step
    rounded = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7), p),
        donate_argnums=0)

    def step(state, tokens, targets):
        state, metrics = real(state.replace(params=rounded(state.params)), tokens, targets)
        return state.replace(params=rounded(state.params)), metrics

    trainer.train_step = step


def _prepared(monkeypatch, change):
    """The Gated DeltaNet layers' input side with `change(q, k, v, g, beta)`
    put after it (the step traces on its first call, with the patch)."""
    from ddp_classification_pytorch_tpu.models import decoder_lm

    real = decoder_lm.gdn_prepare
    monkeypatch.setattr(decoder_lm, "gdn_prepare",
                        lambda *a: change(*real(*a)))


def halved_beta_step(trainer, monkeypatch):
    """beta = sigmoid(.) in (0, 1): the factor 2 of `linear_allow_neg_eigval`
    dropped."""
    _prepared(monkeypatch, lambda q, k, v, g, beta: (q, k, v, g, 0.5 * beta))


def unscaled_query_step(trainer, monkeypatch):
    """q L2-normed and not scaled by d_k^-1/2."""
    _prepared(monkeypatch, lambda q, k, v, g, beta:
              ((q * q.shape[-1] ** 0.5).astype(q.dtype), k, v, g, beta))


def input_norms_step(trainer, monkeypatch):
    """x + Mixer(RMSNorm(x)), x + MLP(RMSNorm(x)): the same leaves, norming
    what goes INTO each sub-layer instead of what comes out."""
    import flax.linen as nn
    import jax
    from ddp_classification_pytorch_tpu.models import decoder_lm

    class PreNormLayer(decoder_lm.DecoderLayer):
        @nn.compact
        def __call__(self, x):
            c = self.cfg
            mix = {"attn": self._attention, "gdn": self._gdn}[self.mixer]
            with jax.named_scope(self.mixer):
                h = decoder_lm.RMSNorm(c.rms_eps, name="norm_mix_out")(x)
                x = x + mix(h.astype(self.dtype))
            with jax.named_scope("ffn"):
                u = decoder_lm.RMSNorm(c.rms_eps, name="norm_ffn_out")(x)
                y = self._gated_mlp(u.astype(self.dtype), c.dense_width, "ffn")
            return x + y.astype(x.dtype), None

    monkeypatch.setattr(decoder_lm, "DecoderLayer", PreNormLayer)


FAULTS = (bf16_parameters_step, halved_beta_step, unscaled_query_step, input_norms_step)


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
    only = os.environ.get("FAULTS", "")     # e.g. FAULTS=bf16_parameters_step
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
