"""torchvision-weight import tests.

1. Primitive-level oracle vs torch (baked-in dependency): conv stride-2
   pad-1, BN eval semantics, and MaxPool(3,2,1) must match our flax modules
   bitwise-closely — this is exactly what the explicit-padding change in
   models/resnet.py guarantees.
2. Structural round-trip: a synthetic torch state_dict covering every leaf of
   the flax resnet18/resnet50 trees converts and merges with no unmapped or
   mismatched leaves.
"""

import numpy as np
import pytest
from tiny import zero_variables

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.models import resnet as R
from ddp_classification_pytorch_tpu.models.import_torch import (
    convert_resnet_state_dict,
    merge_into_variables,
)

torch = pytest.importorskip("torch")


def test_conv_stride2_matches_torch():
    tconv = torch.nn.Conv2d(3, 8, 3, stride=2, padding=1, bias=False)
    x = np.random.default_rng(0).normal(size=(2, 3, 16, 16)).astype(np.float32)
    with torch.no_grad():
        ref = tconv(torch.from_numpy(x)).numpy()

    import flax.linen as nn

    fconv = nn.Conv(8, (3, 3), strides=(2, 2), use_bias=False,
                    padding=[(1, 1), (1, 1)])
    kernel = tconv.weight.detach().numpy().transpose(2, 3, 1, 0)
    out = fconv.apply({"params": {"kernel": jnp.asarray(kernel)}},
                      jnp.asarray(x.transpose(0, 2, 3, 1)))
    np.testing.assert_allclose(
        np.asarray(out).transpose(0, 3, 1, 2), ref, atol=1e-5, rtol=1e-5)


def test_maxpool_matches_torch():
    x = np.random.default_rng(1).normal(size=(2, 3, 15, 15)).astype(np.float32)
    with torch.no_grad():
        ref = torch.nn.functional.max_pool2d(
            torch.from_numpy(x), 3, stride=2, padding=1).numpy()
    import flax.linen as nn

    out = nn.max_pool(jnp.asarray(x.transpose(0, 2, 3, 1)), (3, 3),
                      strides=(2, 2), padding=[(1, 1), (1, 1)])
    np.testing.assert_allclose(
        np.asarray(out).transpose(0, 3, 1, 2), ref, atol=1e-6)


def _torch_key_for(flax_path, leaf):
    """Inverse of import_torch._convert_key, for synthesizing state_dicts."""
    bn_inv = {"scale": "weight", "bias": "bias", "mean": "running_mean",
              "var": "running_var"}
    parts = list(flax_path)
    if parts[0] == "conv_stem":
        return "conv1.weight"
    if parts[0] == "bn_stem":
        return f"bn1.{bn_inv[leaf]}"
    if parts[0] == "fc":
        return f"fc.{'weight' if leaf == 'kernel' else 'bias'}"
    layer, block = parts[0].split("_block")
    prefix = f"{layer}.{block}"
    sub = parts[1]
    if sub == "downsample_conv":
        return f"{prefix}.downsample.0.weight"
    if sub == "downsample_bn":
        return f"{prefix}.downsample.1.{bn_inv[leaf]}"
    if sub.startswith("Conv_"):
        return f"{prefix}.conv{int(sub.split('_')[1]) + 1}.weight"
    if sub.startswith("BatchNorm_"):
        return f"{prefix}.bn{int(sub.split('_')[1]) + 1}.{bn_inv[leaf]}"
    raise AssertionError(flax_path)


def _roundtrip(model, image_size, seed, key_for, to_torch, skipped, converter,
               init_rngs=None):
    """Every leaf of `model`, drawn anew, written under its torch name and
    layout (`to_torch(names, arr)` for the layouts that are not the common
    ones), converted back and merged: each must come back where it was.
    `skipped` are entries of a real state_dict the converter has to drop."""
    variables = zero_variables(model, init_rngs or jax.random.PRNGKey(0),
                               jnp.zeros((1, image_size, image_size, 3)),
                               train=False)
    rng = np.random.default_rng(seed)
    state_dict, expected = dict(skipped), {}
    for coll in ("params", "batch_stats"):
        for path, value in jax.tree_util.tree_flatten_with_path(variables[coll])[0]:
            names = tuple(p.key for p in path)
            arr = rng.normal(size=value.shape).astype(np.float32)
            expected[(coll,) + names] = arr
            special = to_torch(names, arr)
            if special is not None:
                arr = special
            elif names[-1] == "kernel" and arr.ndim == 4:
                arr = arr.transpose(3, 2, 0, 1)  # HWIO → OIHW
            elif names[-1] == "kernel":
                arr = arr.T
            state_dict[key_for(names[:-1], names[-1])] = arr
    merged = merge_into_variables(variables, converter(state_dict))
    for coll in ("params", "batch_stats"):
        for path, value in jax.tree_util.tree_flatten_with_path(merged[coll])[0]:
            names = (coll,) + tuple(p.key for p in path)
            np.testing.assert_array_equal(np.asarray(value), expected[names],
                                          err_msg=str(names))


@pytest.mark.parametrize("arch", ["resnet18", "resnet50"])
def test_state_dict_roundtrip_covers_every_leaf(arch):
    _roundtrip(getattr(R, arch)(num_classes=7, dtype=jnp.float32), 64, 2,
               _torch_key_for, lambda names, arr: None,
               {"bn1.num_batches_tracked": np.int64(5),  # must be skipped
                "mean_vector": np.zeros(3)},             # vestigial buffer
               convert_resnet_state_dict)


def test_pretrained_path_loads_into_train_state(tmp_path):
    """End to end: torch.save a synthetic torchvision-format checkpoint, point
    ModelConfig.pretrained_path at it, and verify the backbone picks it up."""
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state

    model = R.resnet18(num_classes=1000, dtype=jnp.float32)
    variables = zero_variables(model, jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3)), train=False)
    rng = np.random.default_rng(3)
    state_dict = {}
    for coll in ("params", "batch_stats"):
        flat = jax.tree_util.tree_flatten_with_path(variables[coll])[0]
        for path, value in flat:
            names = tuple(p.key for p in path)
            key = _torch_key_for(names[:-1], names[-1])
            arr = rng.normal(size=value.shape).astype(np.float32)
            if names[-1] == "kernel" and arr.ndim == 4:
                state_dict[key] = torch.from_numpy(arr.transpose(3, 2, 0, 1))
            elif names[-1] == "kernel":
                state_dict[key] = torch.from_numpy(arr.T)
            else:
                state_dict[key] = torch.from_numpy(arr)
    ckpt = tmp_path / "rn18.pth"
    torch.save(state_dict, str(ckpt))

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.dtype = "float32"
    cfg.model.pretrained = True
    cfg.model.pretrained_path = str(ckpt)
    cfg.data.image_size = 64
    cfg.data.num_classes = 10  # != 1000 → fc must be skipped, backbone loaded

    mesh = meshlib.make_mesh()
    _, _, state = create_train_state(cfg, mesh, steps_per_epoch=4)
    got = np.asarray(state.params["backbone"]["conv_stem"]["kernel"])
    want = np.asarray(state_dict["conv1.weight"]).transpose(2, 3, 1, 0)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_nested_feat_net_format_converts():
    """Reference NESTED checkpoints key the backbone as feat_net.<seq_idx>.*
    (NetFeat Sequential over [conv1,bn1,relu,maxpool,layer1..4,avgpool],
    NESTED/model/model.py:37-40)."""
    sd = {
        "feat_net.0.weight": np.zeros((64, 3, 7, 7), np.float32),
        "feat_net.1.weight": np.ones((64,), np.float32),
        "feat_net.1.running_mean": np.zeros((64,), np.float32),
        "feat_net.4.0.conv1.weight": np.zeros((64, 64, 3, 3), np.float32),
        "feat_net.4.0.bn1.bias": np.zeros((64,), np.float32),
    }
    out = convert_resnet_state_dict(sd)
    assert out["params"]["conv_stem"]["kernel"].shape == (7, 7, 3, 64)
    assert out["params"]["bn_stem"]["scale"].shape == (64,)
    assert out["batch_stats"]["bn_stem"]["mean"].shape == (64,)
    assert out["params"]["layer1_block0"]["Conv_0"]["kernel"].shape == (3, 3, 64, 64)
    assert out["params"]["layer1_block0"]["BatchNorm_0"]["bias"].shape == (64,)


def test_empty_conversion_raises():
    with pytest.raises(ValueError, match="no convertible"):
        convert_resnet_state_dict({"encoder.blocks.0.w": np.zeros((3, 3))})


def test_pretrained_without_path_raises():
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.pretrained = True  # no pretrained_path
    cfg.data.image_size = 32
    with pytest.raises(ValueError, match="pretrained_path"):
        create_train_state(cfg, meshlib.make_mesh(), steps_per_epoch=1)


def test_merge_rejects_shape_mismatch():
    model = R.resnet18(num_classes=7, dtype=jnp.float32)
    variables = zero_variables(model, jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    bad = {"params": {"conv_stem": {"kernel": np.zeros((3, 3, 3, 63))}}}
    with pytest.raises(ValueError, match="shape mismatch"):
        merge_into_variables(variables, bad)


# ------------------------------------------------------- VGG19-BN import ---

def _vgg_torch_key(flax_path, leaf):
    """Inverse of convert_vgg_state_dict's mapping (torchvision vgg19_bn)."""
    from ddp_classification_pytorch_tpu.models.vgg import _CFG_E

    bn_inv = {"scale": "weight", "bias": "bias", "mean": "running_mean",
              "var": "running_var"}
    name2seq = {}
    seq = i = 0
    for v in _CFG_E:
        if v == "M":
            seq += 1
        else:
            name2seq[f"conv{i}"] = seq
            name2seq[f"bn{i}"] = seq + 1
            seq += 3
            i += 1
    mod = flax_path[0]
    if mod.startswith("conv"):
        return f"features.{name2seq[mod]}.{'weight' if leaf == 'kernel' else 'bias'}"
    if mod.startswith("bn"):
        return f"features.{name2seq[mod]}.{bn_inv[leaf]}"
    cl = {"fc1": "0", "fc2": "3", "fc3": "6"}[mod]
    return f"classifier.{cl}.{'weight' if leaf == 'kernel' else 'bias'}"


def test_vgg_state_dict_roundtrip_covers_every_leaf():
    from ddp_classification_pytorch_tpu.models.import_torch import (
        convert_vgg_state_dict,
    )
    from ddp_classification_pytorch_tpu.models.vgg import vgg19_bn

    def fc1(names, arr):
        if names[-2:] == ("fc1", "kernel"):
            o = arr.shape[1]
            # flax (HWC-flat, O) → torch (O, CHW-flat)
            return (arr.T.reshape(o, 7, 7, 512).transpose(0, 3, 1, 2)
                    .reshape(o, -1))

    _roundtrip(vgg19_bn(num_classes=13, dtype=jnp.float32), 64, 4,
               _vgg_torch_key, fc1,
               {"features.1.num_batches_tracked": np.int64(7)},
               convert_vgg_state_dict,
               init_rngs={"params": jax.random.PRNGKey(0),
                          "dropout": jax.random.PRNGKey(1)})


def test_vgg_fc1_flatten_order_matches_torch():
    """The CHW→HWC input-dim permutation on fc1 must keep the linear layer's
    OUTPUT identical between torch (flattening NCHW) and flax (flattening
    NHWC)."""
    from ddp_classification_pytorch_tpu.models.import_torch import (
        convert_vgg_state_dict,
    )

    rng = np.random.default_rng(5)
    x_nchw = rng.normal(size=(2, 512, 7, 7)).astype(np.float32)
    w = rng.normal(size=(16, 512 * 7 * 7)).astype(np.float32)
    ref = x_nchw.reshape(2, -1) @ w.T  # torch fc1 forward

    conv = convert_vgg_state_dict(
        {"classifier.0.weight": w, "classifier.0.bias": np.zeros(16, np.float32)})
    kernel = conv["params"]["fc1"]["kernel"]
    out = x_nchw.transpose(0, 2, 3, 1).reshape(2, -1) @ kernel  # flax forward
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ TResNet-M import ---

def _tresnet_torch_key(flax_path, leaf):
    """Inverse of convert_tresnet_state_dict's mapping (timm tresnet_m)."""
    import re as _re

    bn_inv = {"scale": "weight", "bias": "bias", "mean": "running_mean",
              "var": "running_var"}
    p = flax_path
    if p[0] == "stem_conv":
        return "body.conv1.0.weight"
    if p[0] == "stem_abn":
        return f"body.conv1.1.{bn_inv[leaf]}"
    if p[0] == "fc":
        return f"head.fc.{'weight' if leaf == 'kernel' else 'bias'}"
    m = _re.fullmatch(r"stage(\d+)_block(\d+)", p[0])
    layer, block = int(m.group(1)), int(m.group(2))
    prefix = f"body.layer{layer}.{block}"
    basic = layer in (1, 2)
    aa_conv = 1 if basic else 2  # conv wrapped with the blur at stride 2
    stride2 = block == 0 and layer >= 2
    sub = p[1]
    if sub.startswith("conv"):
        j = int(sub[4:])
        mid = "0.0" if (stride2 and j == aa_conv) else "0"
        return f"{prefix}.conv{j}.{mid}.weight"
    if sub.startswith("abn") or sub in ("bn2", "bn3"):
        j = int(sub[3:]) if sub.startswith("abn") else int(sub[2:])
        mid = "0.1" if (stride2 and j == aa_conv) else "1"
        return f"{prefix}.conv{j}.{mid}.{bn_inv[leaf]}"
    if sub == "se":
        return f"{prefix}.se.{p[2]}.{'weight' if leaf == 'kernel' else 'bias'}"
    if sub == "downsample":
        return f"{prefix}.downsample.1.0.weight"
    if sub == "bn_down":
        return f"{prefix}.downsample.1.1.{bn_inv[leaf]}"
    raise AssertionError(flax_path)


def test_tresnet_state_dict_roundtrip_covers_every_leaf():
    from ddp_classification_pytorch_tpu.models.import_torch import (
        convert_tresnet_state_dict,
    )
    from ddp_classification_pytorch_tpu.models.tresnet import tresnet_m

    def se(names, arr):
        if names[-1] == "kernel" and len(names) >= 3 and names[-3] == "se":
            return arr.T[:, :, None, None]  # Dense (I, O) → timm 1×1-conv (O, I, 1, 1)

    _roundtrip(tresnet_m(num_classes=11, dtype=jnp.float32), 64, 6,
               _tresnet_torch_key, se,
               # fixed blur buffers + BN counters must be skipped
               {"body.layer2.0.conv1.1.filt": np.zeros((128, 1, 3, 3)),
                "body.conv1.1.num_batches_tracked": np.int64(3)},
               convert_tresnet_state_dict)
