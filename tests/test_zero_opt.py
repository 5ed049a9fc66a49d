"""ZeRO-1 optimizer-state sharding + bf16 gradient reduction: parity and
detectors.

The perf claims live in the committed program baseline
(analysis/baselines.json, regenerated by cli.analyze --update-baseline and
fenced by scripts/lint.sh); what THIS file proves is that the fast program
is the SAME program:

- a dp2 ZeRO-sharded run matches the replicated-optimizer run
  state-for-state (ZeRO-1 changes where the optimizer state LIVES, never
  the update arithmetic);
- the bf16-wire gradient reduction tracks the f32 run within the
  quantization error one bf16 rounding of the gradients implies;
- the asserted-sharded detector FIRES on a deliberately replicated
  optimizer-state row (repo convention: every detector is shown to fire);
- the HLO wire-dtype accounting recovers the bf16 payload from CPU XLA's
  f32-promoted collectives, including through a kLoop fusion;
- the committed baseline actually exhibits the deltas the knobs exist to
  buy (ZeRO peak-HBM cut, bf16 payload halving).
"""

import json
import os

import jax
import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_cfg(**parallel):
    cfg = get_preset("baseline")
    cfg.data.image_size = 32
    cfg.data.num_classes = 4
    cfg.data.batch_size = 16
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    for k, v in parallel.items():
        setattr(cfg.parallel, k, v)
    return cfg


def _dp2_mesh():
    return meshlib.make_mesh(meshlib.MeshSpec(2, 1),
                             devices=jax.devices()[:2])


def _run_steps(cfg, mesh, steps=3, rows=16, seed=3):
    """3 steps of the real train step on a fixed seeded batch (the
    test_two_process_run_matches_single_process pins); returns (losses,
    full host copy of the final state). test_grad_accum.py runs it too."""
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    rng = np.random.default_rng(seed)
    images = rng.normal(size=(rows, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 4, rows).astype(np.int32)
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        batch = meshlib.make_global_array((images, labels), mesh)
        losses = []
        for _ in range(steps):
            state, metrics = step(state, *batch)
            losses.append(float(metrics["loss"]))
    return losses, jax.device_get(state)


def _assert_trees_close(a, b, rtol, atol):
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, _ = jax.tree_util.tree_flatten_with_path(b)
    assert len(la) == len(lb)
    for (path, x), (_, y) in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


def test_zero_sharded_matches_replicated_state():
    """The tentpole parity pin: zero_opt=on partitions every big momentum
    leaf over the data axis, and after 3 steps the WHOLE state (params,
    batch_stats, momentum) matches the replicated-optimizer run within
    f32 reduction-order noise. A real divergence here means the sharded
    update read or wrote the wrong shard."""
    mesh = _dp2_mesh()
    losses_z, state_z = _run_steps(_tiny_cfg(zero_opt="on"), mesh)
    losses_r, state_r = _run_steps(_tiny_cfg(zero_opt="off"), mesh)
    np.testing.assert_allclose(losses_z, losses_r, rtol=2e-4, atol=2e-4)
    _assert_trees_close(state_z, state_r, rtol=2e-4, atol=2e-4)


def test_zero_state_actually_sharded():
    """The parity above would pass trivially if zero_opt=on silently fell
    back to replicated state — pin that the big momentum leaves really
    carry the data axis (the analyzer asserts the same on the audit
    config; this is the in-suite witness on the parity config)."""
    from ddp_classification_pytorch_tpu.train.state import create_train_state

    mesh = _dp2_mesh()
    with mesh:
        _, _, state = create_train_state(_tiny_cfg(zero_opt="on"), mesh,
                                         steps_per_epoch=4)
    n_data = sum(
        1 for leaf in jax.tree_util.tree_leaves(state.opt_state)
        if meshlib.DATA_AXIS in str(getattr(leaf.sharding, "spec", "")))
    assert n_data >= 10, f"only {n_data} momentum leaves data-sharded"


def test_bf16_grad_reduce_tracks_f32():
    """grad_reduce_dtype=bfloat16 quantizes the gradients ONCE (before
    the cross-replica mean; params/momentum stay f32 master copies — the
    torch-AMP analogy in docs/performance.md). bf16 keeps 8 mantissa
    bits, so each step's update carries a ~2^-8 relative perturbation of
    the gradient; after 3 SGD steps on this config an absolute drift
    well under 1e-1 in the loss and 5e-2 in any leaf is the expected
    envelope (measured: ~1e-2 loss drift), while a wiring bug (wrong
    axis, missed cast-back, dropped replica) lands orders of magnitude
    outside it."""
    mesh = _dp2_mesh()
    losses_bf, state_bf = _run_steps(
        _tiny_cfg(zero_opt="off", grad_reduce_dtype="bfloat16"), mesh)
    losses_f, state_f = _run_steps(_tiny_cfg(zero_opt="off"), mesh)
    np.testing.assert_allclose(losses_bf, losses_f, rtol=0.05, atol=0.1)
    _assert_trees_close(state_bf, state_f, rtol=0.1, atol=5e-2)
    # and the two runs must NOT be bit-identical: identical states mean
    # the bf16 cast never happened (the knob silently became a no-op)
    diffs = [
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree_util.tree_leaves(state_bf.params),
                        jax.tree_util.tree_leaves(state_f.params))]
    assert max(diffs) > 0.0, "bf16 wire run bit-identical to f32 — no-op?"


def test_bf16_rejects_unsupported_composition():
    """The bf16 wire path rides a shard_map over the data axis; the
    compositions it can't express must fail loudly at build time, not
    train on silently-f32 wires."""
    from ddp_classification_pytorch_tpu.models.factory import build_model
    from ddp_classification_pytorch_tpu.train.schedule import build_optimizer
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 2),
                             devices=jax.devices()[:4])
    cfg = _tiny_cfg(model_axis=2, grad_reduce_dtype="bfloat16")
    model = build_model(cfg.model, cfg.data.num_classes, mesh=mesh)
    tx = build_optimizer(cfg.optim, 4)
    with pytest.raises(ValueError, match="grad_reduce_dtype"):
        make_train_step(cfg, model, tx, mesh=mesh)
    cfg2 = _tiny_cfg(grad_reduce_dtype="float16")
    with pytest.raises(ValueError, match="grad_reduce_dtype"):
        make_train_step(cfg2, model, tx, mesh=_dp2_mesh())


def test_zero_opt_setting_validation():
    mesh = _dp2_mesh()
    with pytest.raises(ValueError, match="zero_opt"):
        meshlib.zero_opt_enabled("yes", mesh)
    assert meshlib.zero_opt_enabled("auto", mesh)
    assert meshlib.zero_opt_enabled("on", mesh)
    assert not meshlib.zero_opt_enabled("off", mesh)
    one = meshlib.make_mesh(meshlib.MeshSpec(1, 1),
                            devices=jax.devices()[:1])
    # dp=1: nothing to shard over — auto degrades to replicated
    assert not meshlib.zero_opt_enabled("auto", one)


def test_zero_spec_rules():
    """_zero_spec: leaves under the 64 KiB floor stay replicated (their
    all-gather latency would cost more than the HBM saved); bigger leaves
    shard the first free dim divisible by dp; dims already owned by
    another axis are never stolen."""
    mesh = _dp2_mesh()
    sh = meshlib.opt_shardings(
        {"small": np.zeros((4, 4), np.float32),
         "big": np.zeros((64, 3, 3, 64), np.float32)},
        mesh, zero_data=True)
    assert sh["small"].spec == meshlib.P()
    assert tuple(sh["big"].spec) == (meshlib.DATA_AXIS, None, None, None)
    # odd leading dims: the first DIVISIBLE dim is found, not forced
    sh2 = meshlib.opt_shardings(
        {"odd": np.zeros((3, 3, 64, 128), np.float32)}, mesh,
        zero_data=True)
    assert tuple(sh2["odd"].spec) == (None, None, meshlib.DATA_AXIS, None)


def test_zero_detector_fires_on_replicated_opt_state():
    """Repo convention: every detector is shown to FIRE. A synthetic
    sharding-table row with a 2 MiB replicated opt_state buffer must trip
    the asserted-sharded property at the ZeRO cells' 1 MiB threshold —
    and stop firing when the row carries the data axis or the general
    16 MiB threshold applies."""
    from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
        REPLICATED_BYTES, ZERO_OPT_REPLICATED_BYTES, audit_sharding_table)

    mesh = _dp2_mesh()
    rep = meshlib.replicated(mesh)
    data = jax.sharding.NamedSharding(mesh, meshlib.P(meshlib.DATA_AXIS))

    def row(sharding, path=".opt_state[0].mu['conv']['kernel']"):
        return {"path": path, "shape": (3, 3, 256, 256), "dtype": "float32",
                "bytes": 2 * 1024 * 1024, "spec": str(sharding.spec),
                "_sharding": sharding}

    fired = audit_sharding_table(
        [row(rep)], mesh, "t",
        opt_state_threshold=ZERO_OPT_REPLICATED_BYTES)
    assert len(fired) == 1 and fired[0].check == "sharding"
    assert "asserts ZeRO-sharded" in fired[0].message
    # sharded over data: clean
    assert not audit_sharding_table(
        [row(data)], mesh, "t",
        opt_state_threshold=ZERO_OPT_REPLICATED_BYTES)
    # same bytes under the general threshold (non-ZeRO cell): clean —
    # the per-cell override IS what arms the assertion
    assert not audit_sharding_table([row(rep)], mesh, "t")
    # non-opt_state path: general threshold applies even in a ZeRO cell
    big = dict(row(rep, path=".params['w']"), bytes=REPLICATED_BYTES)
    fired = audit_sharding_table(
        [big], mesh, "t", opt_state_threshold=ZERO_OPT_REPLICATED_BYTES)
    assert len(fired) == 1 and "ZeRO-shardable" in fired[0].message
    # dp=1 mesh: nothing is "replicated across the data axis"
    one = meshlib.make_mesh(meshlib.MeshSpec(1, 1),
                            devices=jax.devices()[:1])
    assert not audit_sharding_table(
        [row(meshlib.replicated(one))], one, "t",
        opt_state_threshold=ZERO_OPT_REPLICATED_BYTES)


_PROMOTED_BF16_HLO = """\
HloModule promoted, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%fused_computation.1 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %convert.2 = bf16[8,8]{1,0} convert(f32[8,8]{1,0} %param_0.1)
  ROOT %convert.3 = f32[8,8]{1,0} convert(bf16[8,8]{1,0} %convert.2)
}

ENTRY %main.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %wire_fusion = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p0), kind=kLoop, calls=%fused_computation.1
  %all-reduce.1 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %wire_fusion), replica_groups={{0,1}}, to_apply=%region_0.1
  ROOT %all-reduce.2 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %p0), replica_groups={{0,1}}, to_apply=%region_0.1
}
"""


# the same program as the XLA of jax 0.9.0 prints it: operands carry no
# types, and the all-reduce combiner has merged the two reductions into one
# tuple op whose arrays pair with its operands in order
_PROMOTED_BF16_HLO_COMBINED = """\
HloModule promoted, entry_computation_layout={(f32[8,8]{1,0})->(f32[8,8]{1,0}, f32[8,8]{1,0})}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

%fused_computation.1 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %convert.2 = bf16[8,8]{1,0} convert(%param_0.1), metadata={op_name="jit(step)/shard_map/psum"}
  %convert.3 = f32[8,8]{1,0} convert(%convert.2)
  ROOT %copy.1 = f32[8,8]{1,0} copy(%convert.3)
}

ENTRY %main.1 (p0: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8]{1,0} parameter(0)
  %convert_copy_fusion = f32[8,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  ROOT %all-reduce.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-reduce(%convert_copy_fusion, %p0), channel_id=1, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%region_0.1
}
"""


@pytest.mark.parametrize("hlo,n_ops", [
    pytest.param(_PROMOTED_BF16_HLO, 2, id="typed-operands"),
    pytest.param(_PROMOTED_BF16_HLO_COMBINED, 1, id="untyped-combined"),
])
def test_wire_dtype_accounting_unwraps_promoted_collectives(hlo, n_ops):
    """CPU XLA promotes every sub-f32 collective to its f32-only reduction
    runtime, leaving a convert(f32→bf16)→convert(bf16→f32) round-trip —
    usually folded into the kLoop fusion feeding the op. The inventory
    must charge that array at the bf16 WIRE dtype (what a TPU ships)
    while an un-converted operand keeps full f32 accounting — in either
    HLO text format, and array by array inside a combined op."""
    from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
        collective_inventory, collective_wire_dtypes)

    inv = collective_inventory(hlo)
    ar = inv["kinds"]["all-reduce"]
    full = 8 * 8 * 4
    assert ar["count"] == n_ops
    # array 1 rides the round-trip fusion → bf16 wire (half); array 2 is
    # a genuine f32 reduction → full
    assert ar["bytes"] == full // 2 + full
    assert ar["max_op_bytes"] == (full if n_ops == 2 else full // 2 + full)
    assert inv["total_bytes"] == full // 2 + full
    assert collective_wire_dtypes(hlo) == {
        "all-reduce": {"bf16": 1, "f32": 1}}


def test_committed_baseline_exhibits_zero_deltas():
    """The acceptance criterion made durable: the COMMITTED baseline must
    keep showing (a) the ZeRO cell holding half the momentum per device
    (argument bytes) and a peak-HBM cut vs the replicated anchor (−6.6%
    as XLA:CPU of jax 0.9.0 assigns buffers, where 0.4.37's read −22%:
    the state saving is the same 22.3 MB, the ZeRO program's temporaries
    grew; fenced at ≤0.95×), (b) its weight all-gathers on the data
    axis, (c) the bf16 cell's reduction payload at ~half the anchor's
    (fenced at ≤0.55×), and (d) full donation everywhere —
    regenerating the baseline from a regressed program fails here even
    if --update-baseline banked it."""
    path = os.path.join(
        REPO, "ddp_classification_pytorch_tpu", "analysis",
        "baselines.json")
    programs = json.load(open(path))["programs"]
    zero = programs["train_step@dp2"]
    anchor = programs["train_step_replicated@dp2"]
    bf16 = programs["train_step_bf16@dp2"]

    assert zero["peak_hbm_bytes"] <= 0.95 * anchor["peak_hbm_bytes"]
    # the part of the cut ZeRO-1 owns: momentum is as large as the params
    # the step all-gathers, and a dp=2 device keeps half of it
    ag = zero["collectives"]["all-gather"]
    assert (anchor["arg_bytes"] - zero["arg_bytes"]
            >= 0.45 * ag["bytes"])
    assert ag["bytes"] > 10 * 1024 * 1024
    assert set(ag["axes"]) <= {"data", "model"}
    # the sharded momentum leaves are in the cell's input-sharding digest
    assert sum(1 for p, spec in zero["sharded_leaves"].items()
               if "opt_state" in p and "data" in spec) >= 10
    assert not any("opt_state" in p
                   for p in anchor.get("sharded_leaves", {}))

    ar_bf16 = bf16["collectives"]["all-reduce"]["bytes"]
    ar_anchor = anchor["collectives"]["all-reduce"]["bytes"]
    assert ar_bf16 <= 0.55 * ar_anchor
    assert "bf16" in bf16["wire_dtypes"]["all-reduce"]
    assert bf16["peak_hbm_bytes"] <= 1.01 * anchor["peak_hbm_bytes"]

    for key in ("train_step@dp2", "train_step@dp2tp2",
                "train_step_replicated@dp2", "train_step_bf16@dp2"):
        assert programs[key]["donation_coverage"] == 1.0, key
