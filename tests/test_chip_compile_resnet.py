"""ResNet-50's whole steps at `chip_smoke.py`'s shapes, compiled for a
described v5e (rules and fixtures: chip_compile_common.py)."""

import jax
import jax.numpy as jnp
import pytest
from chip_compile_common import HBM_BYTES, abstract_state, device_bytes, topo  # noqa: F401

from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
    collective_inventory,
    collective_wire_dtypes,
)
from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.steps import (
    make_topk_predict_step,
    make_train_step,
)


def _smoke_cfg(batch: int, reduce_dtype: str = "float32"):
    """chip_smoke.py's shapes: ResNet-50, 224 px, 1000 classes, bf16
    compute, uint8 wire."""
    cfg = get_preset("baseline")
    cfg.model.arch = "resnet50"
    cfg.model.dtype = "bfloat16"
    cfg.data.num_classes = 1000
    cfg.data.image_size = 224
    cfg.data.batch_size = batch
    cfg.data.input_dtype = "uint8"
    cfg.parallel.grad_reduce_dtype = reduce_dtype
    return cfg


def _batch(batch: int, mesh):
    sh = meshlib.batch_sharding(mesh)
    return (jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.uint8, sharding=sh),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sh))


@pytest.fixture(scope="module")
def single(topo):
    """(cfg, mesh, model, tx, abstract state) on one chip: the state's
    shapes do not depend on the batch, so the train step (128 rows) and the
    serve step (8) are lowered from the same one."""
    cfg = _smoke_cfg(batch=128)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        return (cfg, mesh, *abstract_state(cfg, mesh))


def test_resnet50_train_step_fits_one_chip(single):
    cfg, mesh, model, tx, state = single
    with mesh:
        step = make_train_step(cfg, model, tx, mesh=mesh)
        compiled = step.lower(state, *_batch(128, mesh)).compile()
    assert device_bytes(compiled) < HBM_BYTES
    m = compiled.memory_analysis()
    # donation holds on the chip too: every state byte aliases
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes


def test_resnet50_serve_predict_step_compiles(single):
    cfg, mesh, model, _, state = single
    with mesh:
        predict = make_topk_predict_step(cfg, model, 5, mesh=mesh)
        images, _ = _batch(8, mesh)  # the smoke's largest bucket
        compiled = predict.lower(state, images).compile()
    assert device_bytes(compiled) < HBM_BYTES


def test_resnet50_dp4_step(topo):
    """`chip_smoke.py --chips 4`'s program: the gradient crosses the chips
    once, at bf16; optimizer state is sharded under ZeRO-1 (a quarter of
    the momentum per device) and the updated parameters are all-gathered."""
    cfg = _smoke_cfg(batch=512, reduce_dtype="bfloat16")
    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 1), devices=topo.devices)
    with mesh:
        model, tx, state = abstract_state(cfg, mesh)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        images, labels = _batch(512, mesh)
        compiled = step.lower(state, images, labels).compile()
    assert device_bytes(compiled) < HBM_BYTES
    leaf_bytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    param_bytes = leaf_bytes(state.params)
    # the repo's own HLO audit reads the chip's program text as it reads
    # the CPU's (analysis/sharding_audit.py)
    text = compiled.as_text()
    kinds = collective_inventory(text, mesh)["kinds"]
    wire = collective_wire_dtypes(text)
    # bf16 wire: ~2 bytes per parameter all-reduced over the data axis,
    # and no f32 gradient reduction beside it (what is left in f32 is BN
    # statistics and scalars) — an f32 wire would read ~1.0x here
    ar = kinds["all-reduce"]
    assert (0.45 * param_bytes <= ar["axes"]["data"]
            <= 0.55 * param_bytes), (ar, param_bytes)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    assert wire["all-reduce"].get("bf16", 0) >= 0.9 * n_leaves, wire
    # ZeRO-1: parameters come back by all-gather after the sharded update
    assert kinds["all-gather"]["bytes"] >= 0.9 * param_bytes, kinds
    assert set(wire["all-gather"]) == {"f32"}, wire
    # ... and each device is handed a quarter of the momentum, not all of it
    replicated = (leaf_bytes(state) + (images.size + labels.size * 4) // 4)
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args < replicated - 0.5 * leaf_bytes(state.opt_state), (
        args, replicated)
