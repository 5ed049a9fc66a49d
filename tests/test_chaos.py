"""Robustness subsystem tests: fault-spec parsing, every injection point,
the non-finite step sentinel (skip + rc-8 escalation), and the
checksum-verified quarantine-and-fallback resume.

Tier-1-lean by design: the jitted-step tests run on a toy quadratic (no
model build), the checkpoint tests on a 4-float TrainState, and the
supervise.sh tests on the scripted stub interpreter from
test_recovery_rc_discipline. One small Trainer covers the loop wiring.
The full multi-process supervise.sh chaos drill is `slow`
(scripts/chaos_drill.sh).
"""

import os
import stat
import subprocess

import numpy as np
import pytest
from tiny import tiny_cfg

import jax
import jax.numpy as jnp
import optax

from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager
from ddp_classification_pytorch_tpu.train.sentinel import (SentinelDiverged,
                                                           StepSentinel)
from ddp_classification_pytorch_tpu.train.state import TrainState
from ddp_classification_pytorch_tpu.train.steps import _build_step
from ddp_classification_pytorch_tpu.utils import chaos as chaoslib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ parsing --
def test_fault_spec_parses_all_kinds_and_ranges():
    plan = chaoslib.FaultPlan.parse(
        "nan_loss@step=7, ckpt_io@epoch=1, loader_io@batch=3..5, "
        "sigterm@step=20..")
    assert len(plan.faults) == 4 and bool(plan)
    assert plan.windows("nan_loss", "step") == [(7, 7)]
    f = plan.faults[2]
    assert (f.kind, f.unit, f.lo, f.hi) == ("loader_io", "batch", 3, 5)
    assert f.matches(3) and f.matches(5) and not f.matches(6)
    open_ended = plan.faults[3]
    assert open_ended.hi is None and open_ended.matches(10_000)
    # round-trips through str for the "[chaos] fault plan active" log line
    assert chaoslib.FaultPlan.parse(str(plan)).windows("nan_loss") == [(7, 7)]


def test_empty_spec_is_falsy_no_op_plan():
    plan = chaoslib.FaultPlan.parse("")
    assert not plan
    assert plan.should_fire("loader_io", epoch=0, batch=0) is None
    assert plan.windows("nan_loss") == []


@pytest.mark.parametrize("bad", [
    "foo@step=1",          # unknown kind
    "nan_loss@epoch=1",    # nan_loss is keyed by step
    "nan_loss@iter=1",     # unknown unit
    "nan_loss@step=",      # no value
    "nan_loss",            # no condition at all
    "sigterm@step=5..3",   # empty range
])
def test_fault_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        chaoslib.FaultPlan.parse(bad)


def test_env_spec_overrides_config(monkeypatch):
    monkeypatch.setenv(chaoslib.ENV_SPEC, "sigterm@step=9")
    assert chaoslib.resolve_spec("nan_loss@step=1") == "sigterm@step=9"
    monkeypatch.delenv(chaoslib.ENV_SPEC)
    assert chaoslib.resolve_spec("nan_loss@step=1") == "nan_loss@step=1"
    assert chaoslib.resolve_spec("") == ""


def test_host_faults_fire_once_and_persist_across_plans(tmp_path):
    spec = "loader_io@batch=2"
    plan = chaoslib.FaultPlan.parse(spec, state_dir=str(tmp_path))
    assert plan.should_fire("loader_io", epoch=0, batch=2) is not None
    assert plan.should_fire("loader_io", epoch=0, batch=2) is None  # one-shot
    # a "restarted process" (fresh plan, same state_dir) must not re-fire
    plan2 = chaoslib.FaultPlan.parse(spec, state_dir=str(tmp_path))
    assert plan2.should_fire("loader_io", epoch=1, batch=2) is None
    # without a state_dir the firing state is per-process only
    plan3 = chaoslib.FaultPlan.parse(spec)
    assert plan3.should_fire("loader_io", epoch=0, batch=2) is not None


# ---------------------------------------------------------------- sentinel --
def test_sentinel_counts_skips_and_resets_streak():
    lines = []
    s = StepSentinel(max_bad_steps=5, log=lines.append)
    for ok in (1.0, 0.0, 0.0, 1.0, 0.0):
        s.observe(ok)
    s.flush()
    assert s.skipped_total == 3
    assert s.streak == 1  # trailing skip; the 1.0 in between reset it
    assert lines and "skipped 3" in lines[0]
    s.flush()  # empty window: no-op, no new lines
    assert len(lines) == 1


def test_sentinel_raises_on_sustained_streak_across_windows():
    s = StepSentinel(max_bad_steps=4, log=lambda m: None)
    for ok in (0.0, 0.0):
        s.observe(ok)
    s.flush()  # streak 2 — below threshold
    for ok in (0.0, 0.0):
        s.observe(ok)
    with pytest.raises(SentinelDiverged):
        s.flush()  # streak 4, carried across flush windows
    assert SentinelDiverged.exit_code == 8


def test_sentinel_zero_threshold_never_raises():
    s = StepSentinel(max_bad_steps=0, log=lambda m: None)
    for _ in range(50):
        s.observe(0.0)
    s.flush()
    assert s.skipped_total == 50


# -------------------------------------------------------- jitted step guard --
def _toy_step(chaos=None):
    """_build_step over a toy quadratic: no model build, compiles in ms."""
    tx = optax.sgd(0.1, momentum=0.9)
    params = {"w": jnp.linspace(-1.0, 1.0, 8, dtype=jnp.float32)}
    stats = {"m": jnp.ones((2,), jnp.float32)}

    def loss_fn(params, batch_stats, images, labels, rng):
        pred = (images * params["w"]).sum()
        loss = (pred - labels.sum()) ** 2 * 0.1
        return loss, (jax.tree_util.tree_map(lambda m: m + 1.0, batch_stats),
                      jnp.zeros((1,)))

    step = _build_step(tx, jax.random.PRNGKey(0), loss_fn,
                       lambda loss, aux, labels: {"loss": loss}, chaos=chaos)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats=stats, opt_state=tx.init(params))
    images = jnp.arange(8, dtype=jnp.float32)
    labels = jnp.asarray([3], jnp.int32)
    return step, state, images, labels


def _run_steps(step, state, images, labels, n):
    trace = []
    for _ in range(n):
        state, metrics = step(state, images, labels)
        trace.append({
            "w": np.asarray(jax.device_get(state.params["w"])),
            "m": np.asarray(jax.device_get(state.batch_stats["m"])),
            "step": int(state.step),
            "step_ok": float(metrics["step_ok"]),
            "loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
        })
    return trace


def test_nonfinite_step_applies_identity_update():
    plan = chaoslib.FaultPlan.parse("nan_loss@step=1..2")
    step, state, images, labels = _toy_step(chaos=plan)
    t = _run_steps(step, state, images, labels, 4)
    assert [r["step_ok"] for r in t] == [1.0, 0.0, 0.0, 1.0]
    assert np.isnan(t[1]["loss"]) and np.isnan(t[2]["loss"])
    # skipped steps: params AND batch stats bit-identical to the last good
    np.testing.assert_array_equal(t[1]["w"], t[0]["w"])
    np.testing.assert_array_equal(t[2]["w"], t[0]["w"])
    np.testing.assert_array_equal(t[2]["m"], t[0]["m"])
    # ...but the step counter still advances (rng/schedule stream moves on)
    assert [r["step"] for r in t] == [1, 2, 3, 4]
    # and the step after the window trains again
    assert not np.array_equal(t[3]["w"], t[2]["w"])
    assert np.isfinite(t[3]["loss"])


def test_absent_spec_is_bit_transparent():
    """`--fault_spec` absent ⇒ bit-for-bit the uninjected step (the
    depth-0-style equivalence contract): an empty plan, and a plan with
    only host-side faults, compile the IDENTICAL jitted program — no
    injection op exists to perturb even a fusion decision."""
    step_a, state_a, images, labels = _toy_step(chaos=None)
    ta = _run_steps(step_a, state_a, images, labels, 4)
    for spec in ("", "ckpt_io@epoch=9,loader_io@batch=9,sigterm@step=9"):
        plan = chaoslib.FaultPlan.parse(spec)
        step_b, state_b, images, labels = _toy_step(chaos=plan)
        tb = _run_steps(step_b, state_b, images, labels, 4)
        for a, b in zip(ta, tb):
            np.testing.assert_array_equal(a["w"], b["w"])
            np.testing.assert_array_equal(a["m"], b["m"])
            assert a["loss"] == b["loss"] and a["grad_norm"] == b["grad_norm"]
            assert a["step_ok"] == b["step_ok"] == 1.0


def test_out_of_window_nan_injection_never_skips():
    """A compiled-in window that never fires: no skips, same training to
    float tolerance (the extra select can shift XLA fusion by an ULP —
    the semantics, not the bits, are the contract once a window exists)."""
    step_a, state_a, images, labels = _toy_step(chaos=None)
    ta = _run_steps(step_a, state_a, images, labels, 4)
    plan = chaoslib.FaultPlan.parse("nan_loss@step=1000..")
    step_b, state_b, images, labels = _toy_step(chaos=plan)
    tb = _run_steps(step_b, state_b, images, labels, 4)
    for a, b in zip(ta, tb):
        assert a["step_ok"] == b["step_ok"] == 1.0
        np.testing.assert_allclose(a["w"], b["w"], rtol=1e-6, atol=1e-7)
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-6)


# ------------------------------------------------------------------ loader --
def test_loader_io_injection_fires_once_then_recovers():
    from ddp_classification_pytorch_tpu.data.loader import ShardedLoader
    from ddp_classification_pytorch_tpu.data.synthetic import SyntheticDataset

    ds = SyntheticDataset(32, 4, 4, seed=0)
    plan = chaoslib.FaultPlan.parse("loader_io@batch=1")
    loader = ShardedLoader(ds, 8, shuffle=False, num_workers=1,
                           host_id=0, num_hosts=1, chaos=plan)
    with pytest.raises(IOError, match="chaos: injected loader failure"):
        list(loader)
    # one-shot: the "restarted" pass reads every batch cleanly
    assert len(list(loader)) == 4
    loader.close()


# ------------------------------------------- checksums + quarantine/fallback --
def _state(v: float) -> TrainState:
    return TrainState(
        step=jnp.asarray(int(v)),
        params={"w": jnp.full((4,), v)},
        batch_stats={"m": jnp.zeros((2,))},
        opt_state=(),
    )


def test_save_writes_matching_sha256_sidecar(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(1.0), 0, metric=0.5)
    mgr.wait()
    for name in ("ckpt_e0.msgpack", "ckpt_best.msgpack"):
        path = str(tmp_path / name)
        assert os.path.exists(path + ".sha256")
        assert mgr.verify_checkpoint(path) == "ok"


def test_quarantine_and_fallback_to_newest_verified(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(0.0), 0)
    mgr.save(_state(1.0), 1)
    mgr.wait()
    # tear the LATEST checkpoint (torn copy / bit rot / injected ckpt_io)
    p = tmp_path / "ckpt_e1.msgpack"
    data = p.read_bytes()
    p.write_bytes(data[: len(data) // 2])

    mgr2 = CheckpointManager(str(tmp_path))
    restored, next_epoch = mgr2.restore_latest(_state(-1.0))
    # fell back one epoch instead of crashing every restart identically
    assert next_epoch == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.zeros((4,)))
    assert (tmp_path / "ckpt_e1.msgpack.corrupt").exists()
    assert not (tmp_path / "ckpt_e1.msgpack").exists()  # out of the scan
    # the quarantined file stays quarantined on the NEXT restart too
    _, next_epoch = CheckpointManager(str(tmp_path)).restore_latest(_state(-1.0))
    assert next_epoch == 1


def test_legacy_checkpoint_without_sidecar_still_resumes(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(3.0), 0)
    mgr.wait()
    os.remove(str(tmp_path / "ckpt_e0.msgpack.sha256"))
    assert mgr.verify_checkpoint(str(tmp_path / "ckpt_e0.msgpack")) == "legacy"
    restored, next_epoch = CheckpointManager(str(tmp_path)).restore_latest(
        _state(-1.0))
    assert next_epoch == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.full((4,), 3.0))


def test_torn_legacy_checkpoint_is_quarantined_by_deserialization(tmp_path):
    """Pre-checksum torn file: no sidecar to fail, so from_bytes fails —
    auto-resume must quarantine it and fall back, not crash every retry."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(0.0), 0)
    mgr.save(_state(1.0), 1)
    mgr.wait()
    p = tmp_path / "ckpt_e1.msgpack"
    p.write_bytes(p.read_bytes()[:10])
    os.remove(str(p) + ".sha256")  # simulate a pre-checksum run's file

    restored, next_epoch = CheckpointManager(str(tmp_path)).restore_latest(
        _state(-1.0))
    assert next_epoch == 1
    assert (tmp_path / "ckpt_e1.msgpack.corrupt").exists()


def test_explicit_resume_of_corrupt_checkpoint_raises(tmp_path):
    """--resume <corrupt path> is deterministic: ValueError (rc 2 at the
    CLI), not the silent fallback reserved for --auto_resume."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(0.0), 0)
    mgr.wait()
    p = tmp_path / "ckpt_e0.msgpack"
    p.write_bytes(p.read_bytes()[: 8])
    with pytest.raises(ValueError, match="sha256"):
        mgr.restore(_state(-1.0), str(p))


def test_ckpt_io_injection_tears_the_target_epoch_only(tmp_path):
    plan = chaoslib.FaultPlan.parse("ckpt_io@epoch=0")
    mgr = CheckpointManager(str(tmp_path), chaos=plan)
    mgr.save(_state(0.0), 0)
    mgr.save(_state(1.0), 1)
    mgr.wait()
    assert mgr.verify_checkpoint(mgr.epoch_path(0)) == "corrupt"
    assert mgr.verify_checkpoint(mgr.epoch_path(1)) == "ok"  # one-shot
    restored, next_epoch = CheckpointManager(str(tmp_path)).restore_latest(
        _state(-1.0))
    assert next_epoch == 2  # epoch 1 verified; the torn epoch 0 is ignored
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.ones((4,)))


def test_prune_removes_sidecars_with_checkpoints(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=1)
    for e in range(3):
        mgr.save(_state(float(e)), e)
    mgr.wait()
    assert sorted(mgr._epoch_checkpoints()) == [2]
    left = sorted(f for f in os.listdir(tmp_path) if f.endswith(".sha256"))
    assert left == ["ckpt_e2.msgpack.sha256"]


# ------------------------------------------------------------ trainer wiring --
def test_trainer_nan_burst_skips_then_sustained_nan_diverges(tmp_path):
    """One tiny Trainer (ONE train-step compile — this is the expensive
    test of the file), both sentinel behaviors from a two-window plan: a
    bounded NaN burst is skipped and training continues; an open-ended
    window trips SentinelDiverged once the consecutive streak reaches
    max_bad_steps."""
    from ddp_classification_pytorch_tpu.train.loop import Trainer

    cfg = tiny_cfg("baseline", tmp_path, epochs=3)
    cfg.data.image_size = 16
    cfg.data.synthetic_size = 64
    cfg.run.log_every = 2
    # 4 steps/epoch: a burst at steps 1-2 (epoch 0), then NaN forever
    # from step 6 (mid-epoch 1 onward)
    cfg.run.fault_spec = "nan_loss@step=1..2,nan_loss@step=6.."

    tr = Trainer(cfg)
    m = tr.train_epoch(0)  # steps 0-3; 1 and 2 poisoned
    assert m["step_ok"] == pytest.approx(0.5)
    assert tr.sentinel.skipped_total == 2
    assert tr.sentinel.streak == 0  # step 3 was finite and reset it
    # weights were never poisoned by the skipped steps
    assert np.all(np.isfinite(
        np.asarray(jax.device_get(
            jax.tree_util.tree_leaves(tr.state.params)[0]))))

    # sustained divergence: steps 6-7 of epoch 1 and all of epoch 2 are
    # non-finite — the streak carries across the epoch boundary
    tr.sentinel = StepSentinel(3)
    m = tr.train_epoch(1)  # ends with streak 2: below threshold
    assert tr.sentinel.streak == 2 and np.isfinite(m["top1"])
    with pytest.raises(SentinelDiverged):
        tr.train_epoch(2)


# --------------------------------------------------- supervise.sh discipline --
STUB = """#!/usr/bin/env bash
state="${FAKE_STATE:?}"
n=$(cat "$state" 2>/dev/null || echo 0)
n=$((n+1)); echo "$n" > "$state"
rc=$(echo "${FAKE_RCS:?}" | tr ',' '\\n' | sed -n "${n}p")
[ -z "$rc" ] && rc=$(echo "$FAKE_RCS" | tr ',' '\\n' | tail -1)
exit "$rc"
"""


def _stub_env(tmp_path, rcs):
    fakebin = tmp_path / "bin"
    fakebin.mkdir(exist_ok=True)
    stub = fakebin / "python"
    stub.write_text(STUB)
    stub.chmod(stub.stat().st_mode | stat.S_IXUSR)
    env = dict(os.environ)
    env["PATH"] = f"{fakebin}:{env['PATH']}"
    env["FAKE_STATE"] = str(tmp_path / "calls")
    env["FAKE_RCS"] = rcs
    return env


def test_supervise_rc8_is_deterministic_no_restart(tmp_path):
    out = tmp_path / "out"
    env = _stub_env(tmp_path, "8,0")
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "supervise.sh"),
         "baseline", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=30)
    assert p.returncode == 8, (p.returncode, p.stderr)
    assert int((tmp_path / "calls").read_text()) == 1, \
        "rc=8 (diverged) must stop without a restart"
    log = (out / "restarts.log").read_text()
    assert "rc=8" in log and "action=stop" in log


def test_supervise_appends_restart_lines(tmp_path):
    out = tmp_path / "out"
    env = _stub_env(tmp_path, "1,143,0")
    env["RUNTIME_BACKOFF_S"] = "0"
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "supervise.sh"),
         "baseline", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=30)
    assert p.returncode == 0, p.stderr
    lines = (out / "restarts.log").read_text().strip().splitlines()
    # one per non-zero exit, plus the final clean exit (elastic pods
    # reconstruct their world transitions from this log, so the
    # converged state must appear there too)
    assert len(lines) == 3
    assert "rc=1" in lines[0] and "action=restart" in lines[0]
    assert "rc=143" in lines[1] and "attempt=2/" in lines[1]
    assert "rc=0" in lines[2] and "action=exit" in lines[2]


# ------------------------------------------------------------ full drill --
@pytest.mark.slow
def test_full_chaos_drill(tmp_path):
    """The real thing: scripts/chaos_drill.sh drives supervise.sh + the CLI
    through NaN burst / loader IO / torn checkpoint / SIGTERM and asserts
    convergence to rc 0, then sustained NaN to rc 8 with no restart."""
    env = {k: v for k, v in os.environ.items()
           if k not in (chaoslib.ENV_SPEC, chaoslib.ENV_STATE_DIR)}
    env["CHAOS_PHASES"] = "1 2"  # pod phases 3-5 are test_fleet's drill
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "chaos_drill.sh"),
         str(tmp_path / "drill")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-2000:])
    assert "CHAOS DRILL PASS" in p.stdout
