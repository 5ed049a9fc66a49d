"""uint8 dataplane: wire-format propagation, on-device normalization
equivalence, and device-flip determinism.

The uint8 wire (data.input_dtype == "uint8", the default) ships raw pixels
host→device at ¼ the bytes of the legacy normalized-float32 wire and defers
`(x/255 − μ)/σ` (+ the train flip) to a device-side epilogue in the jitted
step. The acceptance contract: `input_dtype == "float32"` preserves the
host-normalize numerics exactly (the epilogue compiles to a no-op for f32
inputs), and the uint8 path matches it to float tolerance on identical
crops — quantization happens pre-normalize in both modes.
"""

import numpy as np
import pytest
import tiny  # noqa: F401  (registers resnet10 and vit_t16_d4)
from PIL import Image

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.data.imagefolder import ImageFolderDataset
from ddp_classification_pytorch_tpu.data.loader import ShardedLoader
from ddp_classification_pytorch_tpu.data.native import NativeBatcher
from ddp_classification_pytorch_tpu.data.device_prefetch import DevicePrefetcher
from ddp_classification_pytorch_tpu.data.synthetic import SyntheticDataset
from ddp_classification_pytorch_tpu.data.transforms import (
    build_transform,
    normalize,
    preset_for_dataset,
)
from ddp_classification_pytorch_tpu.obs import spans
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import (
    device_input_epilogue,
    make_eval_step,
    make_train_step,
)


def _tiny_cfg(input_dtype: str):
    cfg = get_preset("baseline")
    cfg.data.dataset = "synthetic"  # no transform preset → no device flip
    cfg.data.image_size = 32
    cfg.data.num_classes = 4
    cfg.data.batch_size = 16
    cfg.data.input_dtype = input_dtype
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    return cfg


# ------------------------------------------------------------- transforms --

def test_transform_uint8_mode_same_crops_as_float():
    """Identical rng → identical geometry; the uint8 output IS the pre-
    normalize array of the float output (quantization point unchanged)."""
    img = Image.fromarray(
        np.random.default_rng(0).integers(0, 256, (48, 56, 3)).astype(np.uint8))
    for preset, train in [("baseline", False), ("baseline", True),
                          ("cifar", True), ("cdr", True),
                          ("clothing1m", True)]:
        size = 32 if preset == "cifar" else 24
        t_f = build_transform(preset, train, image_size=size, crop_size=40)
        t_u = build_transform(preset, train, image_size=size, crop_size=40,
                              out_dtype="uint8")
        out_f = t_f(img, np.random.default_rng(7))
        out_u = t_u(img, np.random.default_rng(7))
        assert out_u.dtype == np.uint8, preset
        assert out_f.dtype == np.float32, preset
        # float path may additionally host-flip (uint8 defers it to the
        # device); compare against both orientations of the uint8 crop
        ref, ref_flipped = normalize(out_u), normalize(out_u[:, ::-1])
        assert (np.array_equal(out_f, ref)
                or np.array_equal(out_f, ref_flipped)), preset


def test_build_transform_rejects_unknown_dtype():
    with pytest.raises(ValueError, match="input dtype"):
        build_transform("baseline", True, out_dtype="bfloat16")


def test_preset_for_dataset_map():
    assert preset_for_dataset("synthetic", "baseline") is None
    assert preset_for_dataset("imagefolder", "cdr") == "cdr"
    assert preset_for_dataset("cifar10", "baseline") == "cifar"
    assert preset_for_dataset("plc", "baseline") == "clothing1m"


# ---------------------------------------------------------- wire plumbing --

@pytest.mark.parametrize("wire,px_bytes", [("uint8", 1), ("float32", 4)])
def test_loader_and_prefetcher_keep_the_wire_dtype(wire, px_bytes):
    """dataset dtype → host batches → staged global arrays, unchanged: the
    uint8 wire ships 1 B/px (¼ the H2D bytes of float32's 4), labels
    untouched."""
    ds = SyntheticDataset(64, 16, 4, out_dtype=wire)
    img, _ = ds.__getitem__(0)
    assert img.dtype == np.dtype(wire)
    loader = ShardedLoader(ds, 16, shuffle=True, num_workers=1,
                           host_id=0, num_hosts=1)
    try:
        images, labels = next(iter(loader))
        assert images.dtype == np.dtype(wire)
        assert images.shape == (16, 16, 16, 3)
        assert labels.dtype == np.int32
        mesh = meshlib.make_mesh()
        it = iter(DevicePrefetcher(loader, mesh, depth=1))
        try:
            g_images, g_labels = next(it)
            assert g_images.dtype == jnp.dtype(wire)
            assert g_images.nbytes == g_images.size * px_bytes
        finally:
            it.close()
    finally:
        loader.close()


def test_float32_wire_unchanged():
    ds = SyntheticDataset(32, 16, 4)  # default out_dtype
    img, _ = ds.__getitem__(0)
    assert img.dtype == np.float32


# ----------------------------------------------- which path filled a batch --

@pytest.mark.parametrize("wire,native,path", [
    ("uint8", True, "native_u8"), ("float32", True, "native_f32"),
    ("uint8", False, "python"), ("float32", False, "python")])
def test_input_load_spans_name_the_path_that_filled_the_batch(
        tmp_path, wire, native, path):
    """Every `input.load` span carries `path`; a NativeBatcher-backed loader
    also counts `input_native_batches_total{loader,wire}`, with the wire
    read off the batch it produced; the per-sample Python path counts none."""
    if native and not NativeBatcher.available():
        pytest.skip("native dataplane not built on this host")
    rng = np.random.default_rng(3)
    paths = []
    for i in range(8):
        paths.append(str(tmp_path / f"{i}.jpg"))
        Image.fromarray(rng.integers(0, 256, (40, 48, 3)).astype(np.uint8)).save(
            paths[-1], quality=90)
    ds = ImageFolderDataset(
        paths, np.arange(8, dtype=np.int32) % 2, ["a", "b"],
        build_transform("baseline", True, 24, 32, out_dtype=wire))
    batcher = NativeBatcher(ds, "baseline", True, 24, 32, seed=1, num_threads=2,
                            out_dtype=wire) if native else None
    name = f"{path}-{wire}"  # a loader label of this case's own
    loader = ShardedLoader(ds, 4, shuffle=False, num_workers=2, host_id=0,
                           num_hosts=1, batcher=batcher, name=name)
    try:
        batches = list(loader)
    finally:
        loader.close()
    assert len(batches) == 2
    assert all(im.dtype == np.dtype(wire) and im.shape == (4, 32, 32, 3)
               for im, _ in batches)
    loads = [s for s in spans.snapshot()
             if s.name == "input.load" and s.ids["loader"] == name]
    assert [s.ids["step"] for s in loads] == [0, 1]
    assert all(s.ids["path"] == path for s in loads)
    counted = {dict(k[1])["wire"]: n for k, n in spans.counters().items()
               if k[0] == "input_native_batches_total"
               and dict(k[1])["loader"] == name}
    assert counted == ({wire: 2} if native else {})


# ------------------------------------------------------- step equivalence --

def test_uint8_matches_float32_through_real_train_step():
    """Same pixels on both wires → allclose loss/metrics and updated params
    (i.e. gradients) through a REAL jitted train step on the 8-device mesh;
    eval step loss agrees too. Synthetic-config steps have no device flip,
    so the comparison is augmentation-free."""
    rng = np.random.default_rng(0)
    u8 = rng.integers(0, 256, (16, 32, 32, 3)).astype(np.uint8)
    f32 = np.stack([normalize(x) for x in u8])
    labels = rng.integers(0, 4, 16).astype(np.int32)
    valid = np.ones(16, np.float32)
    mesh = meshlib.make_mesh()

    outs = {}
    for wire, imgs in [("uint8", u8), ("float32", f32)]:
        cfg = _tiny_cfg(wire)
        model, tx, state = create_train_state(cfg, mesh, 8)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        ev = make_eval_step(cfg, model, mesh=mesh)
        g = meshlib.make_global_array((imgs, labels, valid), mesh)
        ev_out = jax.device_get(ev(state, *g))
        state, metrics = step(state, g[0], g[1])
        outs[wire] = (jax.device_get(metrics), jax.device_get(state.params),
                      ev_out)

    m_u, p_u, e_u = outs["uint8"]
    m_f, p_f, e_f = outs["float32"]
    for k in m_f:
        np.testing.assert_allclose(m_u[k], m_f[k], rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_u),
                    jax.tree_util.tree_leaves(p_f)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(e_u["loss_sum"], e_f["loss_sum"],
                               rtol=1e-5, atol=1e-4)
    assert e_u["top1"] == e_f["top1"]


def test_float32_epilogue_is_identity():
    """The f32 wire must compile to exactly the legacy program — the
    epilogue returns the input object untouched."""
    x = jnp.ones((2, 4, 4, 3), jnp.float32)
    assert device_input_epilogue(x, jax.random.PRNGKey(0), flip=True) is x


# ------------------------------------------------------------ device flip --

def test_device_flip_deterministic_under_fixed_key():
    rng = np.random.default_rng(1)
    u8 = rng.integers(0, 256, (64, 8, 8, 3)).astype(np.uint8)
    key = jax.random.PRNGKey(5)
    a = np.asarray(device_input_epilogue(jnp.asarray(u8), key, flip=True))
    b = np.asarray(device_input_epilogue(jnp.asarray(u8), key, flip=True))
    np.testing.assert_array_equal(a, b)
    # a different step key draws a different mask (P[same] = 2^-64)
    c = np.asarray(device_input_epilogue(
        jnp.asarray(u8), jax.random.PRNGKey(6), flip=True))
    assert (a != c).any()
    # every row is the normalized crop or its exact width-mirror, and with
    # 64 samples both orientations occur
    ref = np.stack([normalize(x) for x in u8])
    flipped_rows = 0
    for i in range(len(u8)):
        if np.array_equal(a[i], ref[i]):
            continue
        np.testing.assert_array_equal(a[i], ref[i][:, ::-1])
        flipped_rows += 1
    assert 0 < flipped_rows < len(u8)


def test_train_step_flip_gate_follows_preset():
    """imagefolder configs (a transform preset exists) flip on-device;
    synthetic configs don't — checked via the step's determinism across
    identical states (flip draws from the step key, so same state ⇒ same
    output either way; the uint8/float32 metric agreement above would
    break if the synthetic path flipped only one wire)."""
    from ddp_classification_pytorch_tpu.train.steps import _train_flip_enabled

    assert _train_flip_enabled(_tiny_cfg("uint8")) is False
    cfg = _tiny_cfg("uint8")
    cfg.data.dataset = "imagefolder"
    assert _train_flip_enabled(cfg) is True
    cfg.data.input_dtype = "float32"  # host already flipped
    assert _train_flip_enabled(cfg) is False
