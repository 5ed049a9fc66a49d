"""Mid-run hang watchdog (train/heartbeat.py::StepHeartbeat).

A device sync that never returns raises no exception. supervise.sh restarts
on EXIT only, so a hang that never exits defeats the whole
failure-detection chain (SURVEY §5); the heartbeat converts the hang into
exit code 7, which supervise.sh + --auto_resume then recover exactly like
a preemption (tests/test_preemption_recovery.py proves that half).

os._exit in a daemon thread cannot be tested in-process — each case runs
in a subprocess.
"""

import os
import subprocess
import sys
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(src: str, timeout: float = 30.0) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", src], cwd=REPO,
                          capture_output=True, timeout=timeout, env=env)


def test_hang_exits_7_with_diagnostic():
    p = _run(
        "import time\n"
        "from ddp_classification_pytorch_tpu.train.heartbeat import StepHeartbeat\n"
        "StepHeartbeat(0.3, where='trainer[test]').start()\n"
        "time.sleep(20)\n"  # the simulated hang: no touch ever lands
    )
    assert p.returncode == 7, (p.returncode, p.stderr[-300:])
    assert b"no progress" in p.stderr and b"trainer[test]" in p.stderr


def test_touches_keep_it_alive_and_stop_disarms():
    p = _run(
        "import time\n"
        "from ddp_classification_pytorch_tpu.train.heartbeat import StepHeartbeat\n"
        "hb = StepHeartbeat(0.4).start()\n"
        "for _ in range(10):\n"
        "    time.sleep(0.1); hb.touch()\n"  # slow but alive: must not fire
        "hb.stop()\n"
        "time.sleep(1.0)\n"  # disarmed: silence past the timeout is fine
        "print('survived')\n"
    )
    assert p.returncode == 0, p.stderr[-300:]
    assert b"survived" in p.stdout


def test_zero_timeout_is_inert():
    p = _run(
        "import time\n"
        "from ddp_classification_pytorch_tpu.train.heartbeat import StepHeartbeat\n"
        "hb = StepHeartbeat(0.0).start()\n"  # the default: watchdog off
        "assert hb._thread is None\n"
        "time.sleep(0.5); hb.touch()\n"  # touch on an inert heartbeat is safe
        "print('inert')\n"
    )
    assert p.returncode == 0, p.stderr[-300:]
    assert b"inert" in p.stdout
