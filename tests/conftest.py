"""Force an 8-device CPU topology before any JAX backend initializes.

This makes every test exercise the real jit + NamedSharding + collective code
paths on a virtual 8-device mesh — the TPU-native answer to "test multi-node
without a cluster" (the reference has no tests at all; SURVEY §4).

`jax.config.update` works at any point before first backend use, and
XLA_FLAGS is read lazily at CPU-client creation.

The suite's time budget and the rule that keeps it (a test builds the
smallest program on which its assertion can fail) are in tests/README.md;
the limit below is what holds every wait to it.
"""

import faulthandler
import os
import signal
import threading

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402

TEST_LIMIT_S = 300  # the longest test reads 135 to 185 s under six workers (tests/README.md)


@pytest.fixture(autouse=True)
def _every_test_has_a_limit():
    """A test that runs past TEST_LIMIT_S fails, with every thread's stack
    on stderr, instead of eating the suite's clock (pytest-timeout is not
    installed). The alarm raises in the main thread as soon as it runs
    Python again; the dump comes from a watchdog thread, so it also shows a
    main thread stuck inside a compile or a `wait()` with no timeout."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"test ran past {TEST_LIMIT_S} s (tests/conftest.py)")

    was = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=False)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


@pytest.fixture(scope="session", autouse=True)
def _jit_registration_guard():
    """Every `jax.jit` site in train/steps.py must be reachable from a
    factory registered in jaxpr_audit.build_registry (or a documented
    delegate/exempt): an unregistered jit site is a hot program the
    donation/collective/dtype audits silently never see. Session-wide so
    the guard trips on ANY test run, not just the analysis file's."""
    from ddp_classification_pytorch_tpu.analysis.lint import lint_jit_sites

    findings = lint_jit_sites()
    assert not findings, (
        "unregistered jax.jit site(s) in train/steps.py — register the "
        "factory in jaxpr_audit.build_registry() or document it in "
        "analysis.lint._JIT_EXEMPT:\n"
        + "\n".join(str(f) for f in findings))
