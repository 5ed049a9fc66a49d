"""Native C++ dataplane tests: builds the shared lib, decodes real JPEGs and
PNGs, and checks transform semantics against the Python/PIL pipeline."""


import ctypes
import os
import time

import numpy as np
import pytest
from PIL import Image

from ddp_classification_pytorch_tpu.data.imagefolder import ImageFolderDataset
from ddp_classification_pytorch_tpu.data.native import (
    NativeBatcher,
    get_lib,
    native_decodes_png,
    native_load_batch,
)
from ddp_classification_pytorch_tpu.data.transforms import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    build_transform,
)

# PNG tests only apply to a full build; the JPEG-only -DDP_NO_PNG fallback
# (hosts without libpng) is supported-degraded, not broken. The probe is a
# fixture, not a module-level skipif value, so collection never triggers
# the g++ build — only actually-selected PNG tests pay for it.
@pytest.fixture
def png_support():
    if not native_decodes_png():
        pytest.skip("native dataplane built without libpng (JPEG-only fallback)")


def _pil_val_ref(im, out=224, short=256):
    """The shared PIL oracle for the val transform: resize short side to
    `short` (BILINEAR), center-crop `out`, ImageNet-normalize."""
    im = im.convert("RGB")
    w, h = im.size
    s = short / min(w, h)
    im2 = im.resize((round(w * s), round(h * s)), Image.BILINEAR)
    left = (im2.width - out) // 2
    top = (im2.height - out) // 2
    ref = np.asarray(im2.crop((left, top, left + out, top + out)), np.float32)
    return (ref / 255.0 - IMAGENET_MEAN) / IMAGENET_STD


@pytest.fixture(scope="module")
def jpegs(tmp_path_factory):
    root = tmp_path_factory.mktemp("jpegs")
    paths = []
    for i, (w, h) in enumerate([(320, 240), (200, 300), (256, 256), (64, 48)]):
        # smooth gradient + color so bilinear comparisons are stable
        x = np.broadcast_to(np.linspace(0, 1, w)[None, :], (h, w))
        y = np.broadcast_to(np.linspace(0, 1, h)[:, None], (h, w))
        img = np.stack([x * 255, y * 255, (x + y) / 2 * 255], axis=2).astype(np.uint8)
        p = str(root / f"img{i}.jpg")
        Image.fromarray(img).save(p, quality=95)
        paths.append(p)
    return paths


def test_native_lib_builds():
    assert get_lib() is not None, "native dataplane failed to build"


def test_val_transform_matches_pil_center_crop(jpegs):
    out, errors = native_load_batch(jpegs, out_size=224, train=False,
                                    resize_short=256, seed=1, num_threads=2)
    assert errors == 0
    assert out.shape == (len(jpegs), 224, 224, 3)
    for i, p in enumerate(jpegs):
        with Image.open(p) as im:
            ref = _pil_val_ref(im)
        # different resample order (resize-then-crop vs fused) and no
        # antialiasing → tolerance in normalized units
        diff = np.abs(out[i] - ref).mean()
        assert diff < 0.12, (i, diff)


def test_train_transform_is_deterministic_and_varied(jpegs):
    a1, e1 = native_load_batch(jpegs, 224, train=True, seed=7, num_threads=2)
    a2, e2 = native_load_batch(jpegs, 224, train=True, seed=7, num_threads=1)
    b, _ = native_load_batch(jpegs, 224, train=True, seed=8, num_threads=2)
    assert e1 == e2 == 0
    np.testing.assert_array_equal(a1, a2)  # same seed → same crops, any thread count
    assert np.abs(a1 - b).mean() > 1e-3    # different seed → different crops


@pytest.fixture(scope="module")
def pngs(tmp_path_factory):
    """RGB, RGBA and palette PNGs — the transform branches of the native
    decoder (PIL convert('RGB') is the semantics oracle)."""
    root = tmp_path_factory.mktemp("pngs")
    x = np.broadcast_to(np.linspace(0, 1, 300)[None, :], (260, 300))
    y = np.broadcast_to(np.linspace(0, 1, 260)[:, None], (260, 300))
    base = np.stack([x * 255, y * 255, (x + y) / 2 * 255], 2).astype(np.uint8)
    paths = []
    rgb = str(root / "rgb.png")
    Image.fromarray(base).save(rgb)
    paths.append(rgb)
    rgba = str(root / "rgba.png")
    Image.fromarray(
        np.concatenate([base, np.full((260, 300, 1), 200, np.uint8)], 2)
    ).save(rgba)  # 4-channel uint8 → RGBA inferred (mode= arg is deprecated)
    paths.append(rgba)
    pal = str(root / "palette.png")
    Image.fromarray(base).convert("P", palette=Image.ADAPTIVE).save(pal)
    paths.append(pal)
    return paths, base


def test_png_decode_matches_pil(pngs, png_support):
    paths, _ = pngs
    out, errors = native_load_batch(paths, out_size=224, train=False,
                                    resize_short=256, seed=2, num_threads=2)
    assert errors == 0
    for i, p in enumerate(paths):
        with Image.open(p) as im:
            ref = _pil_val_ref(im)
        diff = np.abs(out[i] - ref).mean()
        # palette quantization gets a little extra slack
        assert diff < 0.15, (i, p, diff)


def test_png_16bit_rescales_not_clamps(tmp_path, pngs, png_support):
    """16-bit PNGs: libpng's strip_16 rescales (v*257 >> 8 == v) — the
    correct reading. PIL's convert('RGB') CLAMPS >255 instead, so the
    oracle here is the original 8-bit content, not PIL."""
    _, base = pngs
    gray = base[:, :, 0]
    p = str(tmp_path / "sixteen.png")
    # uint16 array → I;16 inferred (the mode= arg is deprecated in Pillow)
    Image.fromarray(gray.astype(np.uint16) * 257).save(p)
    out, errors = native_load_batch([p], out_size=224, train=False,
                                    resize_short=256, seed=2, num_threads=1)
    assert errors == 0
    ref = _pil_val_ref(Image.fromarray(gray))
    assert np.abs(out[0] - ref).mean() < 0.12


def test_mixed_jpeg_png_batch(jpegs, pngs, png_support):
    out, errors = native_load_batch([jpegs[0], pngs[0][0]], 96, train=True,
                                    seed=5, num_threads=2)
    assert errors == 0
    assert np.abs(out).sum(axis=(1, 2, 3)).min() > 0.0


def test_truncated_png_reported_not_crashed(tmp_path, pngs, png_support):
    """Valid PNG signature + corrupt image data drives libpng's longjmp
    error path (the one that must not leak or crash); the slot is
    zero-filled and reported like any other decode failure."""
    with open(pngs[0][0], "rb") as f:
        head = f.read(200)  # signature + IHDR + the start of IDAT
    bad = str(tmp_path / "truncated.png")
    with open(bad, "wb") as f:
        f.write(head)
    out, errors = native_load_batch([bad, pngs[0][0]], 96, train=False, seed=0,
                                    num_threads=2)
    assert errors == 1
    assert np.abs(out[0]).sum() == 0.0
    assert np.abs(out[1]).sum() > 0.0


def _write_adam7_png(path, rgb):
    """Hand-encode a genuinely Adam7-interlaced PNG (Pillow silently
    ignores save(..., interlace=True), so a real fixture must be built by
    hand or the multi-pass decode loop ships untested)."""
    import struct
    import zlib

    h, w, _ = rgb.shape

    def chunk(tag, data):
        body = tag + data
        return (struct.pack(">I", len(data)) + body
                + struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF))

    # IHDR: 8-bit RGB, interlace method 1 (Adam7)
    ihdr = struct.pack(">IIBBBBB", w, h, 8, 2, 0, 0, 1)
    passes = [(0, 0, 8, 8), (4, 0, 8, 8), (0, 4, 4, 8), (2, 0, 4, 4),
              (0, 2, 2, 4), (1, 0, 2, 2), (0, 1, 1, 2)]
    raw = bytearray()
    for x0, y0, dx, dy in passes:
        sub = rgb[y0::dy, x0::dx]
        if sub.size == 0:
            continue
        for row in sub:
            raw.append(0)  # filter type None per scanline
            raw.extend(row.tobytes())
    with open(path, "wb") as f:
        f.write(b"\x89PNG\r\n\x1a\n" + chunk(b"IHDR", ihdr)
                + chunk(b"IDAT", zlib.compress(bytes(raw)))
                + chunk(b"IEND", b""))


def test_interlaced_png_decodes(tmp_path, pngs, png_support):
    _, base = pngs
    p = str(tmp_path / "interlaced.png")
    _write_adam7_png(p, base)
    with Image.open(p) as probe:  # the fixture really is interlaced
        assert probe.info.get("interlace") == 1
        np.testing.assert_array_equal(np.asarray(probe.convert("RGB")), base)
    out, errors = native_load_batch([p], out_size=224, train=False,
                                    resize_short=256, seed=2, num_threads=1)
    assert errors == 0
    ref = _pil_val_ref(Image.fromarray(base))
    assert np.abs(out[0] - ref).mean() < 0.12


def test_bad_file_reported_and_zero_filled(tmp_path, jpegs):
    bad = str(tmp_path / "not_a.jpg")
    with open(bad, "wb") as f:
        f.write(b"this is not a jpeg")
    out, errors = native_load_batch([jpegs[0], bad], 96, train=False, seed=0)
    assert errors == 1
    assert np.abs(out[1]).sum() == 0.0
    assert np.abs(out[0]).sum() > 0.0


def test_dimension_bomb_header_reported_not_crashed(tmp_path, pngs, png_support):
    """A valid PNG signature declaring absurd dimensions (header bomb) must
    be rejected BEFORE allocation — an std::bad_alloc escaping a pool
    thread would std::terminate the whole trainer instead of degrading to
    the zero-fill + PIL-retry contract."""
    import struct
    import zlib

    def chunk(tag, data):
        body = tag + data
        return (struct.pack(">I", len(data)) + body
                + struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF))

    bomb = str(tmp_path / "bomb.png")
    ihdr = struct.pack(">IIBBBBB", 1_000_000, 1_000_000, 8, 2, 0, 0, 0)
    with open(bomb, "wb") as f:
        f.write(b"\x89PNG\r\n\x1a\n" + chunk(b"IHDR", ihdr)
                + chunk(b"IDAT", zlib.compress(b"\x00" * 16))
                + chunk(b"IEND", b""))
    out, errors = native_load_batch([bomb, pngs[0][0]], 96, train=False,
                                    seed=0, num_threads=2)
    assert errors == 1
    assert np.abs(out[0]).sum() == 0.0
    assert np.abs(out[1]).sum() > 0.0


# ------------------------------------------------------------- uint8 wire --
def _float_batch(paths, size, train, seed, threads, mean, std):
    """`dp_load_batch` called directly with a mean/std pair of the test's own
    (the Python wrapper only ever passes ImageNet's)."""
    fp = ctypes.POINTER(ctypes.c_float)
    out = np.empty((len(paths), size, size, 3), np.float32)
    arr = (ctypes.c_char_p * len(paths))(*[os.fsencode(p) for p in paths])
    errors = get_lib().dp_load_batch(
        arr, len(paths), out.ctypes.data_as(fp), size, size, int(train), 256,
        0.8, 1.0, ctypes.c_uint64(seed), (ctypes.c_float * 3)(*mean),
        (ctypes.c_float * 3)(*std), threads)
    return out, errors


@pytest.mark.parametrize("size", [224, 97, 33])
@pytest.mark.parametrize("seed", [5, 4294967291])
@pytest.mark.parametrize("kind", ["jpeg", "png", "mixed"])
@pytest.mark.parametrize("threads", [1, 2, 8])
@pytest.mark.parametrize("train", [True, False], ids=["train", "val"])
def test_uint8_batch_is_the_quantized_float_batch(
        request, jpegs, train, threads, kind, seed, size):
    """The uint8 wire, byte for byte: what the C workers write equals
    `clip(rint(x), 0, 255)` of the float batch under the identity pair
    mean 0, std 1/255 — the composition the Python side ran before the
    workers quantized themselves. Both come out of the one resample kernel,
    so this pins the float store too."""
    if kind == "jpeg":
        paths = jpegs
    else:
        request.getfixturevalue("png_support")
        pngs = request.getfixturevalue("pngs")[0]
        paths = pngs if kind == "png" else jpegs[:2] + pngs + jpegs[2:]
    got, errors = native_load_batch(paths, size, train, seed=seed,
                                    num_threads=threads, out_dtype="uint8")
    raw, raw_errors = _float_batch(paths, size, train, seed, threads,
                                   (0.0,) * 3, (1.0 / 255.0,) * 3)
    assert errors == raw_errors == 0
    assert got.dtype == np.uint8 and got.shape == raw.shape
    np.testing.assert_array_equal(
        got, np.clip(np.rint(raw), 0, 255).astype(np.uint8))
    # and the float32 wire is the same call with ImageNet's pair
    f32, _ = native_load_batch(paths, size, train, seed=seed, num_threads=threads)
    ref, _ = _float_batch(paths, size, train, seed, threads,
                          IMAGENET_MEAN, IMAGENET_STD)
    assert f32.dtype == np.float32
    np.testing.assert_array_equal(f32, ref)


def test_the_two_seeds_of_the_uint8_test_flip_some_images_and_not_others(jpegs):
    """Red rises with x in every fixture JPEG, so a falling red ramp is a
    flipped image: both seeds of the byte-identity test hold both kinds."""
    for seed in (5, 4294967291):
        out, _ = native_load_batch(jpegs, 97, train=True, seed=seed,
                                   out_dtype="uint8")
        red = out[..., 0].astype(np.int32)
        flipped = (red[:, :, -8:].mean(axis=(1, 2)) < red[:, :, :8].mean(axis=(1, 2)))
        assert flipped.any() and not flipped.all(), (seed, flipped)
    val, _ = native_load_batch(jpegs, 97, train=False, seed=5, out_dtype="uint8")
    red = val[..., 0].astype(np.int32)
    assert (red[:, :, -8:].mean(axis=(1, 2)) > red[:, :, :8].mean(axis=(1, 2))).all()


def test_bad_file_uint8_zero_filled_counted_and_patched_by_pil(tmp_path, jpegs):
    """A file the C side declines (a BMP: neither magic) in uint8 mode: the
    slot is zero bytes and counted; `NativeBatcher` re-loads it through the
    dataset's PIL transform into a uint8 row, the other rows untouched."""
    bmp = str(tmp_path / "really_a.bmp")
    with Image.open(jpegs[0]) as im:
        im.save(bmp)
    paths = [jpegs[0], bmp, jpegs[1]]
    out, errors = native_load_batch(paths, 64, train=False, seed=0,
                                    num_threads=2, out_dtype="uint8")
    assert errors == 1 and out.dtype == np.uint8
    assert not out[1].any() and out[0].any() and out[2].any()

    ds = ImageFolderDataset(
        paths, np.zeros(3, np.int32), ["c0"],
        build_transform("baseline", False, 64, 72, out_dtype="uint8"))
    batcher = NativeBatcher(ds, "baseline", False, 64, 72, seed=0,
                            num_threads=2, out_dtype="uint8")
    images, labels = batcher(np.arange(3), 0, 0)
    assert images.dtype == np.uint8 and images.shape == (3, 64, 64, 3)
    assert labels.dtype == np.int32
    direct, _ = native_load_batch(paths, 64, train=False, resize_short=72,
                                  seed=batcher.seed * 1_000_003 & 0xFFFFFFFF,
                                  num_threads=2, out_dtype="uint8")
    np.testing.assert_array_equal(images[[0, 2]], direct[[0, 2]])
    # the patched row is the same picture as its JPEG twin in row 0, through
    # PIL instead of libjpeg (the BMP holds the decoded JPEG's pixels)
    assert images[1].any()
    assert np.abs(images[1].astype(np.int32) - images[0]).mean() < 4.0


def test_native_load_batch_rejects_an_unknown_dtype(jpegs):
    with pytest.raises(ValueError, match="out_dtype"):
        native_load_batch(jpegs, 32, train=False, out_dtype="bfloat16")


def _fresh_native(monkeypatch, native_mod, **attrs):
    for k, v in dict(_lib=None, _load_failed=False, build_error="",
                     **attrs).items():
        monkeypatch.setattr(native_mod, k, v)


def test_library_is_keyed_by_source_hash_not_mtime(tmp_path, monkeypatch):
    """A binary left over from another source — newer mtime, even the old
    fixed name — is never loaded: the path is a hash of dataplane.cpp and
    the flags, so get_lib builds its own file next to the stale one."""
    import subprocess

    from ddp_classification_pytorch_tpu.data import native as native_mod

    stale_src = tmp_path / "stale.cpp"
    stale_src.write_text(
        'extern "C" int dp_load_batch() { return -1; }\n')  # no dp_has_png
    stale_lib = tmp_path / "libdataplane.so"
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(stale_lib),
                    str(stale_src)], check=True, timeout=120)
    future = time.time() + 3600
    os.utime(stale_lib, (future, future))

    _fresh_native(monkeypatch, native_mod, _LIB_DIR=str(tmp_path))
    lib = native_mod.get_lib()
    assert lib is not None, native_mod.build_error
    assert lib.dp_has_png() in (0, 1)
    built = [p.name for p in tmp_path.glob("libdataplane.*.so")]
    assert len(built) == 1 and built[0] in {
        os.path.basename(native_mod._lib_path(v))
        for v in native_mod._LINK_VARIANTS}
    # the key moves with the source and with the flags
    other = tmp_path / "other.cpp"
    other.write_text("// different source\n")
    monkeypatch.setattr(native_mod, "_SRC", str(other))
    assert os.path.basename(native_mod._lib_path(
        native_mod._LINK_VARIANTS[0])) not in built
    assert (native_mod._lib_path(native_mod._LINK_VARIANTS[0])
            != native_mod._lib_path(native_mod._LINK_VARIANTS[1]))


def test_failed_build_surfaces_the_compiler_message(tmp_path, monkeypatch):
    """The PIL fallback stays, but never silently: a build that fails
    leaves the compiler's own words in `build_error`."""
    from ddp_classification_pytorch_tpu.data import native as native_mod

    broken = tmp_path / "broken.cpp"
    broken.write_text("this is not C++ at all;\n")
    _fresh_native(monkeypatch, native_mod, _SRC=str(broken),
                  _LIB_DIR=str(tmp_path))
    assert native_mod.get_lib() is None
    assert "error" in native_mod.build_error
    assert "broken.cpp" in native_mod.build_error
    assert not list(tmp_path.glob("*.so")), "a failed build left a library"
