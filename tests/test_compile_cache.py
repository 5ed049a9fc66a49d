"""utils/cache.py: the compile cache is placed from outside or at one fixed
path inside the checkout, and stays off on the CPU.

Each case runs in a child process: the function writes process-wide jax
config, and the suite's own process must keep the cache off."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SRC = """
import json, jax
from ddp_classification_pytorch_tpu.utils import cache
jax.config.update("jax_platforms", {platform!r})
returned = cache.enable_persistent_cache()
print(json.dumps({{"returned": returned,
                  "config_dir": jax.config.jax_compilation_cache_dir,
                  "enabled": jax.config.jax_enable_compilation_cache,
                  "default": cache.DEFAULT_CACHE_DIR}}))
"""


def _run(platform: str, env_dir: str | None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run([sys.executable, "-c", _SRC.format(platform=platform)],
                       cwd=REPO, env=env, capture_output=True, timeout=120)
    assert p.returncode == 0, p.stderr[-500:]
    return json.loads(p.stdout.decode().strip().splitlines()[-1])


def test_variable_set_means_no_directory_set_in_code(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: the config value is what
    the variable gave it, and the function only reports it."""
    got = _run("tpu", str(tmp_path))
    assert got["returned"] == str(tmp_path)
    assert got["config_dir"] == str(tmp_path)  # JAX's own read of the env
    assert got["enabled"] is True


def test_variable_unset_means_the_fixed_in_checkout_path():
    got = _run("tpu", None)
    assert got["returned"] == got["default"] == got["config_dir"]
    assert got["default"] == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_cpu_means_off(env_dir):
    """The CPU exclusion holds however the cache would have been placed."""
    got = _run("cpu", env_dir)
    assert got["returned"] == ""
    assert got["enabled"] is False
    assert got["config_dir"] in (None, env_dir)  # never the in-code path


def test_no_code_path_sets_the_directory_when_the_variable_is_set():
    """Static guard for the acceptance line: exactly one place in the repo
    writes `jax_compilation_cache_dir`, behind the env check."""
    hits = []
    for root in ("ddp_classification_pytorch_tpu", "scripts"):
        for d, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(d, f) for f in files if f.endswith(".py")]
    hits += [os.path.join(REPO, f)
             for f in ("chip_smoke.py", "__graft_entry__.py")]
    writers = []
    for path in hits:
        with open(path) as f:
            if '"jax_compilation_cache_dir"' in f.read():
                writers.append(os.path.relpath(path, REPO))
    assert writers == ["ddp_classification_pytorch_tpu/utils/cache.py"]
