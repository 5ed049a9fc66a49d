"""chip_smoke.py's CPU rehearsal: same control flow as the chip run, small
shapes. The script is the JAX-free parent of one child per phase, so the
test runs it the way the driver does — as a child process."""

import json
import os
import subprocess
import sys
import urllib.error

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired as e:  # say where it stood, not only that
        pytest.fail(f"chip_smoke.py {' '.join(args)} ran past {timeout} s "
                    f"after saying:\n{str(e.stdout or '')[-3000:]}")
    lines = p.stdout.strip().splitlines()
    return p, lines


# what each phase says it did, on a line before the last
PHASE_SAYS = {
    "train": ("train_synthetic: steps=16", "step_ok=all", "profiler trace",
              "dataplane: native", "compile_s="),
    "serve": ("serve_cold:", "warm boot hit the AOT bank",
              "POST /predict answered", "compile_s="),
    "kernels": ("[kernels] bn_leaky_relu", "[kernels] flash_attention",
                "[kernels] kda (1, 128, 2, 128) float32"),
}


@pytest.mark.parametrize("phase", PHASE_SAYS)
def test_cpu_tiny_rehearsal_end_to_end(phase):
    """The whole rehearsal, a phase a case (`--phases`, as a chip run repeats
    one), in the script's own order: `serve` boots from the checkpoint that
    `train` left under .chip_smoke/, and `loadfile` keeps a file's cases on
    one worker, in order. Each of the five children imports JAX and compiles
    anew: 60 s together alone, 80 to 300 s and more under six workers (one
    run went past the limit of tests/conftest.py without saying where), so
    each phase, two children at most, has its own limit and says where it
    stood."""
    p, lines = _smoke("--platform", "cpu", "--tiny", "--phases", phase,
                      timeout=280)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-1000:]
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 1
    assert '"platform": "tpu"' not in p.stdout
    for needle in PHASE_SAYS[phase]:
        assert needle in p.stdout, needle


def test_forced_phase_failure_is_nonzero_and_says_ok_false(tmp_path):
    """The serve phase alone, with no trainer checkpoint to serve, fails —
    and a failed phase is a non-zero exit and `"ok": false`, never a
    result line."""
    import shutil

    shutil.rmtree(os.path.join(REPO, ".chip_smoke", "train_synthetic"),
                  ignore_errors=True)
    p, lines = _smoke("--platform", "cpu", "--tiny", "--phases", "serve",
                      timeout=120)
    assert p.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert "phase serve: FAILED" in p.stdout
    rec = json.load(open(os.path.join(REPO, ".chip_smoke",
                                      "chip_smoke.json")))
    assert rec["ok"] is False and "serve" in rec["failures"][0]


def _boom(exc):
    def phase(s, platform, result):
        raise exc
    return phase


@pytest.mark.parametrize("exc", [
    KeyError("topk"),
    urllib.error.HTTPError("http://127.0.0.1:1/predict", 500, "boom", {},
                           None),
    TimeoutError("timed out"),
    json.JSONDecodeError("Expecting value", "KERNELS_JSON", 0),
], ids=lambda e: type(e).__name__)
def test_any_exception_in_a_phase_ends_in_the_ok_false_line(
        exc, monkeypatch, tmp_path, capsys):
    """Not only PhaseFailed: an HTTP error, a malformed answer or an OSError
    inside a phase is a failed phase — the last line is still the
    contract's object with `"ok": false`, the exit code 1, and the CPU
    rehearsal's record stays out of chiprun_out/ (a chip run's evidence)."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path / "out"))
    monkeypatch.setitem(chip_smoke.PHASES, "train", _boom(exc))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--platform", "cpu",
                                      "--tiny", "--phases", "train"])
    assert chip_smoke.main() == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": False, "device": None}
    assert f"phase train: FAILED — {type(exc).__name__}" in "\n".join(lines)
    rec = json.load(open(tmp_path / "work" / "chip_smoke.json"))
    assert rec["ok"] is False and type(exc).__name__ in rec["failures"][0]
    assert not (tmp_path / "out").exists()
