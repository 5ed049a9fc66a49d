"""`train/state.py::create_train_state`: the initial state is ONE jitted
program whose outputs are born in their final shardings.

Three things are held here:

- set-up stays free of eager device ops: building a state compiles at most
  three backend programs (the seed's key is two tiny ones, the state is the
  third), whatever the head or the family. An eager `model.init` is dozens
  to hundreds, each compiled again at every process start;
- the values do not depend on the mesh: one seed gives one set of parameters
  on `data=1`, `data=4` and under a class-sharded head;
- every leaf carries exactly the `NamedSharding` the rules in
  `parallel/mesh.py` give it, ZeRO-1 on and off, so restore can place a
  state saved on one topology onto a template built on another.
"""

import jax
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args
from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.utils import cache as progcache


def _image_cfg(workload="baseline", arch="resnet10", mp=1, zero_opt="auto"):
    cfg = get_preset(workload)
    cfg.data.image_size = 32
    cfg.data.num_classes = 64
    cfg.data.batch_size = 16
    cfg.model.arch = arch
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.parallel.model_axis = mp
    cfg.parallel.arcface_sharded_ce = workload == "arcface" and mp > 1
    cfg.parallel.zero_opt = zero_opt
    return cfg


def _decoder_cfg():
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens",
            "--dtype", "float32", "--optimizer", "adam", "--head_block", "16",
            "--vocab_size", "96", "--hidden_size", "32", "--num_layers", "4",
            "--num_heads", "4", "--num_kv_heads", "2", "--head_dim", "8",
            "--expert_width", "16", "--num_experts", "8", "--experts_held", "4",
            "--top_k", "2", "--window", "8", "--seq_len", "32"]
    return config_from_args(build_parser().parse_args(argv))


CASES = {
    "resnet": lambda: _image_cfg(),
    "arcface": lambda: _image_cfg("arcface"),
    "nested": lambda: _image_cfg("nested"),
    "vit": lambda: _image_cfg(arch="vit_t16_d4"),
    "decoder_lm": _decoder_cfg,
}


def _mesh(dp, mp=1):
    return meshlib.make_mesh(meshlib.MeshSpec(dp, mp), jax.devices()[:dp * mp])


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_state_is_at_most_three_backend_programs(case):
    cfg = CASES[case]()
    mesh = _mesh(2)
    # nothing this process compiled earlier may stand in for an eager op
    jax.clear_caches()
    before, _ = progcache.compiled()
    _, _, state = create_train_state(cfg, mesh, steps_per_epoch=4)
    jax.block_until_ready(state)
    built = progcache.compiled()[0] - before
    assert 1 <= built <= 3, (
        f"{built} backend programs for one {case} state: an eager op has "
        "crept into set-up (every process start compiles each again)")
    assert int(state.step) == 0
    assert jax.tree_util.tree_leaves(state.params)


def _expected_shardings(state, mesh, zero):
    rep = meshlib.replicated(mesh)
    return state.replace(
        step=rep,
        params=meshlib.param_shardings(state.params, mesh),
        batch_stats=jax.tree_util.tree_map(lambda _: rep, state.batch_stats),
        opt_state=meshlib.opt_shardings(state.opt_state, mesh, zero_data=zero))


def _assert_leaf_shardings(state, mesh, zero):
    want = _expected_shardings(state, mesh, zero)
    got, _ = jax.tree_util.tree_flatten_with_path(state)
    want = jax.tree_util.tree_leaves(
        want, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    assert len(got) == len(want)
    for (path, leaf), sharding in zip(got, want):
        where = jax.tree_util.keystr(path)
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding), where
        assert leaf.sharding.mesh == mesh, where
        assert leaf.sharding.spec == sharding.spec, (
            f"{where}: {leaf.sharding.spec} != {sharding.spec}")


def _assert_same_values(a, b):
    la, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(x, y, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("zero_opt", ["on", "off"])
def test_same_seed_same_state_on_any_mesh_in_the_rules_shardings(zero_opt, tmp_path):
    """data=1 against data=4 (plain head) and against data=4 x model=2 (the
    class-sharded ArcFace head), leaf shardings checked on each; then the
    one-device state is saved and restored into the wide template."""
    for workload, dp, mp in (("baseline", 4, 1), ("arcface", 4, 2)):
        one, wide = _mesh(1), _mesh(dp, mp)
        _, _, narrow = create_train_state(
            _image_cfg(workload, zero_opt=zero_opt), one, steps_per_epoch=4)
        _, _, template = create_train_state(
            _image_cfg(workload, mp=mp, zero_opt=zero_opt), wide, steps_per_epoch=4)
        zero = zero_opt == "on"
        _assert_leaf_shardings(narrow, one, zero=False)   # dp=1: identity
        _assert_leaf_shardings(template, wide, zero=zero)
        _assert_same_values(narrow.params, template.params)
        _assert_same_values(narrow.batch_stats, template.batch_stats)
        if zero:
            specs = [x.sharding.spec for x in
                     jax.tree_util.tree_leaves(template.opt_state)]
            assert any(meshlib.DATA_AXIS in s for s in specs)
        if mp > 1:
            w = template.params["margin"]["weight"]
            assert w.sharding.spec[0] == meshlib.MODEL_AXIS

        # a state that has moved, so that restore is seen to bring it back
        moved = narrow.replace(
            step=narrow.step + 7,
            params=jax.tree_util.tree_map(lambda x: x + 1.0, narrow.params))
        ckpt = CheckpointManager(str(tmp_path / f"{workload}_{zero_opt}"),
                                 async_save=False)
        ckpt.save(moved, epoch=0, metric=0.0)
        ckpt.wait()
        restored = ckpt.restore(template, ckpt.epoch_path(0))
        assert int(restored.step) == 7
        _assert_same_values(moved.params, restored.params)
        _assert_leaf_shardings(restored, wide, zero=zero)
