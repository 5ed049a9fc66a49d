"""Behavioral tests for the recovery chain's exit-code discipline.

Round-3 advisor (medium): rc=1 used to mean BOTH a deterministic config
error and any unhandled runtime exception, so `supervise.sh` stopped the
whole chain on transient crashes (an XlaRuntimeError, in-process
OOM, dataloader IO) that `--auto_resume` exists to absorb. The contract
now is:

- rc 2 — deterministic config/usage error (argparse uses 2; the trainer
  maps its own config validation to SystemExit(2) BEFORE any backend
  use). supervise.sh stops immediately: restarting replays the bug.
- bare rc 1 — unhandled runtime exception. Retryable with
  ``RUNTIME_BACKOFF_S`` backoff (default 30 s).
- rc 3 — backend unreachable, long ``OUTAGE_BACKOFF_S`` backoff.

The supervise tests drive the real script with a stub `python`
on PATH whose per-call exit codes come from ``FAKE_RCS`` — no backend,
no sleeps (backoffs are env-zeroed), so the suite stays fast.
"""

import os
import stat
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STUB = """#!/usr/bin/env bash
state="${FAKE_STATE:?}"
n=$(cat "$state" 2>/dev/null || echo 0)
n=$((n+1)); echo "$n" > "$state"
[ -n "${FAKE_STDOUT:-}" ] && echo "$FAKE_STDOUT"
rc=$(echo "${FAKE_RCS:?}" | tr ',' '\\n' | sed -n "${n}p")
[ -z "$rc" ] && rc=$(echo "$FAKE_RCS" | tr ',' '\\n' | tail -1)
exit "$rc"
"""


def _stub_env(tmp_path, rcs, stdout=""):
    fakebin = tmp_path / "bin"
    fakebin.mkdir(exist_ok=True)
    stub = fakebin / "python"
    stub.write_text(STUB)
    stub.chmod(stub.stat().st_mode | stat.S_IXUSR)
    env = dict(os.environ)
    env["PATH"] = f"{fakebin}:{env['PATH']}"
    env["FAKE_STATE"] = str(tmp_path / "calls")
    env["FAKE_RCS"] = rcs
    if stdout:
        env["FAKE_STDOUT"] = stdout
    return env


def _calls(tmp_path):
    return int((tmp_path / "calls").read_text())


def test_supervise_retries_runtime_rc1(tmp_path):
    """A transient runtime crash (bare rc 1) restarts with backoff."""
    env = _stub_env(tmp_path, "1,0")
    env["RUNTIME_BACKOFF_S"] = "0"
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "supervise.sh"), "baseline"],
        env=env, capture_output=True, text=True, timeout=30)
    assert p.returncode == 0, p.stderr
    assert _calls(tmp_path) == 2, "rc=1 must be retried, then succeed"
    assert "restart 1/" in p.stderr


def test_supervise_stops_on_config_rc2(tmp_path):
    """A deterministic config/usage error must NOT be retried."""
    env = _stub_env(tmp_path, "2,0")
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "supervise.sh"), "baseline"],
        env=env, capture_output=True, text=True, timeout=30)
    assert p.returncode == 2, (p.returncode, p.stderr)
    assert _calls(tmp_path) == 1, "rc=2 must stop without a restart"


def test_supervise_gives_up_after_max_restarts(tmp_path):
    env = _stub_env(tmp_path, "1,1,1")
    env["RUNTIME_BACKOFF_S"] = "0"
    env["MAX_RESTARTS"] = "2"
    p = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "supervise.sh"), "baseline"],
        env=env, capture_output=True, text=True, timeout=30)
    assert p.returncode == 1
    assert _calls(tmp_path) == 3  # initial + 2 restarts
    assert "giving up" in p.stderr


def test_trainer_config_error_exits_2():
    """Config validation exits 2 before any probe/backend work (and argparse
    usage errors already exit 2), so supervisors see one deterministic code."""
    p = subprocess.run(
        [sys.executable, "-m", "ddp_classification_pytorch_tpu.cli.train",
         "baseline", "--folder", "/tmp/nonexistent",
         "--moe_experts", "4", "--moe_aux_weight", "-1"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert "config error" in p.stderr


def test_trainer_construction_config_error_exits_2():
    """Config-shaped ValueErrors raised during Trainer construction (here:
    MeshSpec.resolve "mesh does not cover N devices" for a --dp that doesn't
    divide the device count) must ALSO map to rc 2 — a bare rc 1 would make
    supervise.sh replay the deterministic bug MAX_RESTARTS times (ADVICE r4)."""
    p = subprocess.run(
        [sys.executable, "-m", "ddp_classification_pytorch_tpu.cli.train",
         "baseline", "--dataset", "synthetic", "--dp", "3", "--epochs", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert "config error" in p.stderr
    assert "does not cover" in p.stderr


def _main_rc(argv, capsys):
    """Drive cli.train.main in-process (the suite already runs on the
    8-device CPU mesh, and `--platform cpu` skips the backend probe) and
    return (exit code, stderr) — each construction-time case costs one
    Trainer build attempt, not a fresh interpreter + jax import."""
    import pytest

    from ddp_classification_pytorch_tpu.cli.train import main

    with pytest.raises(SystemExit) as exc:
        main(argv)
    return exc.value.code, capsys.readouterr().err


def test_pipeline_arch_rejection_exits_2(capsys, tmp_path):
    """build_model's pipeline rejection (--pp_microbatches on a non-ViT
    arch) is config-shaped and deterministic → rc 2, not a bare rc 1
    supervise.sh would replay with backoff (ADVICE r4)."""
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--platform", "cpu",
         "--pp_microbatches", "2", "--epochs", "1",
         "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "requires a ViT" in err


def test_pipeline_head_rejection_exits_2(capsys, tmp_path):
    """build_model's pipeline HEAD rejection (--pp_microbatches supports
    fc/arcface only; the nested preset's head is 'nested') is config-shaped
    and deterministic → rc 2 (ADVICE r4: the remaining named construction
    errors all map like the arch rejection above)."""
    rc, err = _main_rc(
        ["nested", "--dataset", "synthetic", "--model", "vit_t16",
         "--platform", "cpu", "--pp_microbatches", "2", "--epochs", "1",
         "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "supports head=" in err


def test_pipeline_dropout_rejection_exits_2(capsys, tmp_path):
    """build_model's pipeline DROPOUT rejection (the tick loop carries no
    per-tick rng) must exit 2 from Trainer construction too."""
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--model", "vit_t16",
         "--dropout", "0.1", "--platform", "cpu", "--pp_microbatches", "2",
         "--epochs", "1", "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "does not support dropout" in err


def test_hybrid_dcn_plus_pp_rejection_exits_2(capsys, tmp_path):
    """make_hybrid_mesh's dcn+pp rejection (the hybrid mesh is two-axis)
    must exit 2 from Trainer construction too."""
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--platform", "cpu",
         "--dcn_slices", "2", "--pp_microbatches", "2", "--pp_stages", "2",
         "--epochs", "1", "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "does not compose" in err


def test_malformed_fleet_env_exits_2(capsys, tmp_path, monkeypatch):
    """A malformed FLEET_* launch env is deterministic — every restart
    replays the same bad value — so it must exit rc 2 with the offending
    key NAMED, not dissolve into rc 6 rendezvous retries."""
    monkeypatch.setenv("FLEET_COORDINATOR", "localhost:12345")
    monkeypatch.setenv("FLEET_NUM_PROCESSES", "two")
    monkeypatch.setenv("FLEET_PROCESS_ID", "0")
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--platform", "cpu",
         "--multihost", "--epochs", "1", "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "FLEET_NUM_PROCESSES" in err


def test_fleet_coordinator_without_port_exits_2(capsys, tmp_path, monkeypatch):
    monkeypatch.setenv("FLEET_COORDINATOR", "localhost")
    monkeypatch.setenv("FLEET_NUM_PROCESSES", "2")
    monkeypatch.setenv("FLEET_PROCESS_ID", "0")
    rc, err = _main_rc(
        ["baseline", "--dataset", "synthetic", "--platform", "cpu",
         "--multihost", "--epochs", "1", "--out", str(tmp_path)], capsys)
    assert rc == 2, err[-500:]
    assert "config error" in err
    assert "host:port" in err
