"""Force an 8-device CPU topology before any JAX backend initializes.

This makes every test exercise the real jit + NamedSharding + collective code
paths on a virtual 8-device mesh — the TPU-native answer to "test multi-node
without a cluster" (the reference has no tests at all; SURVEY §4).

`jax.config.update` works at any point before first backend use, and
XLA_FLAGS is read lazily at CPU-client creation.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


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
