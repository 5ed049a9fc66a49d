"""Compile for the chip, without the chip: what the `test_chip_compile*.py`
files share.

The TPU's compiler is installed here and compiles for a DESCRIBED v5e:2x2
(`/opt/skills/guides/on-chip-measurement` §2): what interpret mode cannot
show — a block the Mosaic compiler refuses, a program that does not fit the
device, a collective that is not where it should be — fails here at no chip
time. Nothing runs, so these say nothing about results or times.

Rules the files keep: the topology is described inside a module-scoped
fixture (never at import, in a skipif, in parametrize arguments or in
conftest.py; not autouse), everything built from it is built in fixtures or
tests, and the compilation cache is off around the compiles. One file a
model family (kernels, ResNet-50, the decoders), so that `--dist loadfile`
can hand them to different workers: each worker then loads the TPU library,
which it allows only under ALLOW_MULTIPLE_LIBTPU_LOAD (set below; nothing
here opens a chip). `_interpret()` sees the CPU here, so the tests steer it
with monkeypatch — not an option of the program.
"""

import importlib
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import (
    create_train_state,
    state_shardings,
)

HBM_BYTES = 16 * 10 ** 9  # one v5e chip (Google Cloud "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels(monkeypatch):
    """The kernel modules with interpret mode steered off (ops/__init__
    re-exports a function named like the flash module, hence importlib);
    returns the first two, the delta rule's (ops/kda.py) and the whole-row
    attention pair's (ops/rows_attention.py) are steered only."""
    pk = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.pallas_kernels")
    fa = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.flash_attention")
    kda = importlib.import_module("ddp_classification_pytorch_tpu.ops.kda")
    rows = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.rows_attention")
    for module in (pk, fa, kda, rows):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    return pk, fa


def abstract_state(cfg, mesh):
    """(model, tx, TrainState of ShapeDtypeStructs sharded as the trainer
    shards it). A described device holds no array, so the state is traced
    with eval_shape and the repo's own sharding rules are applied to the
    shapes."""
    box = {}

    def build():
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=8)
        box["model"], box["tx"] = model, tx
        return state

    shape = jax.eval_shape(build)
    shardings = state_shardings(
        shape, mesh, meshlib.zero_opt_enabled(cfg.parallel.zero_opt, mesh))
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, shardings)
    return box["model"], box["tx"], state


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
