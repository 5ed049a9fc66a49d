"""K-step gradient accumulation: parity, amortized-comms evidence, and the
`grad-accum-indivisible` construction rejections.

The perf claim lives in the committed program baseline (the
`train_step_accum4*` cells in analysis/baselines.json: ONE data-axis
gradient reduction per optimizer step, payload flat vs the K=1 anchor
while per-microbatch reduction bytes fall ÷K, ÷2K composed with the bf16
wire). What THIS file proves:

- state-for-state parity: K=4 × mb=8 reproduces the K=1 × batch=32 run
  within f32 reduction-order noise after 3 optimizer steps — the scanned
  accumulator computes the SAME mean gradient, just in K partial sums
  (pinned on a LayerNorm model: BatchNorm's per-microbatch batch stats
  make K>1 a genuinely different — not wrong, different — program);
- the banked cells keep exhibiting the amortization the knob buys,
  so regenerating the baseline from a regressed program fails here even
  if --update-baseline banked it;
- every named rejection exits rc 2 through cli.train's config-error
  mapping (in-process, same pattern as test_recovery_rc_discipline).
"""

import functools
import json
import os

import jax
import numpy as np
import pytest
from test_zero_opt import _assert_trees_close, _dp2_mesh, _run_steps as zero_opt_steps
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINES = os.path.join(REPO, "ddp_classification_pytorch_tpu",
                         "analysis", "baselines.json")


def _tiny_vit_cfg(grad_accum=1):
    """LayerNorm-normalized model, dropout off: the configs where K=4 and
    K=1 are the same mathematical function (resnet BN would compute
    per-microbatch batch statistics — correct accumulation semantics,
    but not bit-comparable to the full-batch run)."""
    cfg = get_preset("baseline")
    cfg.data.image_size = 32
    cfg.data.num_classes = 4
    cfg.data.batch_size = 32
    cfg.model.arch = "vit_t16_d4"
    cfg.model.dtype = "float32"
    cfg.model.dropout = 0.0
    cfg.parallel.grad_accum = grad_accum
    return cfg


_run_steps = functools.partial(zero_opt_steps, rows=32, seed=7)


def test_accum4_matches_single_batch_state_for_state():
    """The tentpole parity pin: K=4 × mb=8 (global) and K=1 × batch=32
    run the SAME update — the scan accumulates K partial-mean gradients
    into f32 and the single deferred cross-replica mean reproduces the
    full-batch mean gradient — so after 3 optimizer steps the whole
    state (params, opt_state) agrees within f32 reduction-order noise.
    A real divergence here means the accumulator mis-weighted a
    microbatch or the deferred reduction ran on the wrong values."""
    mesh = _dp2_mesh()
    losses_k4, state_k4 = _run_steps(_tiny_vit_cfg(grad_accum=4), mesh)
    losses_k1, state_k1 = _run_steps(_tiny_vit_cfg(grad_accum=1), mesh)
    np.testing.assert_allclose(losses_k4, losses_k1, rtol=2e-4, atol=2e-4)
    _assert_trees_close(state_k4, state_k1, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_accum4_bf16_wire_tracks_f32_accum():
    """The two levers compose: K=4 with the bf16 wire quantizes only the
    ONE summed-gradient reduction (accumulator stays f32), so the run
    tracks the f32-wire K=4 run within the same one-rounding envelope
    test_bf16_grad_reduce_tracks_f32 pins for K=1. Slow-marked: two extra
    full scan-program compiles on top of the tier-1 parity pin; the
    composed cell's banked wire evidence stays tier-1 just below."""
    mesh = _dp2_mesh()
    cfg_bf = _tiny_vit_cfg(grad_accum=4)
    cfg_bf.parallel.zero_opt = "off"
    cfg_bf.parallel.grad_reduce_dtype = "bfloat16"
    cfg_f = _tiny_vit_cfg(grad_accum=4)
    cfg_f.parallel.zero_opt = "off"
    losses_bf, state_bf = _run_steps(cfg_bf, mesh)
    losses_f, state_f = _run_steps(cfg_f, mesh)
    np.testing.assert_allclose(losses_bf, losses_f, rtol=0.05, atol=0.1)
    _assert_trees_close(state_bf, state_f, rtol=0.1, atol=5e-2)


def test_banked_accum_cells_amortize_the_wire():
    """The acceptance criterion made durable on the COMMITTED baseline:
    the accumulated step's data-axis gradient reduction happens ONCE per
    optimizer step — its total all-reduce payload stays ~flat vs the K=1
    anchor (a per-microbatch reduction would bank ~K× the bytes), which
    IS the ÷K per-microbatch amortization — the bf16-wire cell halves it
    again (÷2K compound), and donation stays full everywhere."""
    programs = json.load(open(BASELINES))["programs"]
    anchor = programs["train_step@dp2"]
    acc = programs["train_step_accum4@dp2"]
    acc_tp = programs["train_step_accum4@dp2tp2"]
    acc_bf = programs["train_step_accum4_bf16@dp2"]

    ar_anchor = anchor["collectives"]["all-reduce"]["bytes"]
    ar = acc["collectives"]["all-reduce"]
    # one reduction per optimizer step: payload parity with the anchor
    # (0.95–1.05×), i.e. per-microbatch bytes = anchor ÷ 4
    assert set(ar["axes"]) == {"data"}
    assert 0.95 * ar_anchor <= ar["bytes"] <= 1.05 * ar_anchor
    # ZeRO-1 still rides the same boundary: one data-axis param
    # all-gather per optimizer step, not per microbatch
    ag = acc["collectives"]["all-gather"]
    assert set(ag["axes"]) == {"data"}
    assert ag["bytes"] <= 1.05 * anchor["collectives"]["all-gather"]["bytes"]

    # composed with the tp axis the head gather joins in, data-axis
    # payload stays amortized
    assert (acc_tp["collectives"]["all-reduce"]["axes"]["data"]
            <= 1.05 * ar_anchor)

    # bf16 wire on the SUMMED grads: ≤0.55× the f32 anchor — the ÷2K
    # compound — and it matches the K=1 bf16 cell (same wire, same bytes
    # to 0.1%: in the K=1 program this XLA shares one all-reduce between
    # each downsample BN's bias gradient and its twin on the main branch,
    # 1792 B in all, which the scanned sum does not expose)
    ar_bf = acc_bf["collectives"]["all-reduce"]["bytes"]
    ar_bf_k1 = programs["train_step_bf16@dp2"][
        "collectives"]["all-reduce"]["bytes"]
    assert ar_bf <= 0.55 * ar_anchor
    assert abs(ar_bf - ar_bf_k1) <= 0.001 * ar_bf_k1
    assert "bf16" in acc_bf["wire_dtypes"]["all-reduce"]

    for key in ("train_step_accum4@dp2", "train_step_accum4@dp2tp2",
                "train_step_accum4_bf16@dp2"):
        assert programs[key]["donation_coverage"] == 1.0, key


def test_scan_rejects_ragged_microbatch_at_trace_time():
    """The meshless scan helper's own guard (the last line of defense
    behind the construction-time rejection): a batch K cannot slice
    evenly must raise, not silently re-weight the remainder."""
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.train.steps import _scan_microbatches

    def loss_fn(params, stats, x, y, rng):
        loss = jnp.mean((x.sum(axis=(1, 2, 3)) - y) ** 2)
        return loss, (stats, jnp.zeros((x.shape[0], 4), jnp.float32))

    params = {"w": jnp.ones((2,), jnp.float32)}
    x = jnp.zeros((6, 4, 4, 3), jnp.float32)
    y = jnp.zeros((6,), jnp.float32)
    with pytest.raises(ValueError, match="grad-accum-indivisible"):
        _scan_microbatches(loss_fn, 4, params, {}, x, y,
                           jax.random.PRNGKey(0))


# ------------------------------------------------- rc-2 construction errors --

def _main_rc(argv, capsys):
    """Drive cli.train.main in-process (the suite already runs on the
    8-device CPU mesh; `--platform cpu` skips the backend probe) and
    return (exit code, stderr)."""
    from ddp_classification_pytorch_tpu.cli.train import main

    with pytest.raises(SystemExit) as exc:
        main(argv)
    return exc.value.code, capsys.readouterr().err


def test_indivisible_batch_rejection_exits_2(capsys, tmp_path):
    """--grad_accum that cannot slice the per-replica batch into equal
    microbatches is deterministic config damage → rc 2 with the named
    `grad-accum-indivisible` error, before any probe or compile."""
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--platform", "cpu",
         "-b", "8", "--grad_accum", "3", "--epochs", "1",
         "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "grad-accum-indivisible" in err
    assert "equal microbatches" in err


def test_pipeline_compose_rejection_exits_2(capsys, tmp_path):
    """grad_accum > 1 + the pipeline schedule: two owners of the
    microbatch loop → rc 2, named, up front."""
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--model", "vit_t16",
         "--platform", "cpu", "--pp_microbatches", "2",
         "--grad_accum", "2", "--epochs", "1", "--out", str(tmp_path)],
        capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "grad-accum-indivisible" in err
    assert "pipeline" in err


def test_sharded_ce_compose_rejection_exits_2(capsys, tmp_path):
    """grad_accum > 1 + arcface_sharded_ce: the partial-FC loss is its
    own shard_map program the accumulation scan cannot slice → rc 2."""
    rc, err = _main_rc(
        ["arcface", "--dataset", "synthetic", "--platform", "cpu",
         "--mp", "2", "--sharded_ce", "--num_classes", "8", "-b", "8",
         "--grad_accum", "2", "--epochs", "1", "--out", str(tmp_path)],
        capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "grad-accum-indivisible" in err
    assert "arcface_sharded_ce" in err
