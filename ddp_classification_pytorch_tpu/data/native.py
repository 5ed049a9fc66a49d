"""ctypes binding + loader integration for the native C++ dataplane.

The reference feeds GPUs with torch DataLoader worker *processes* running
PIL/torchvision per sample (BASELINE/main.py:130-131). Here the host hot path
is one C call per batch (`native/dataplane.cpp`): libjpeg/libpng decode
(dispatch on magic bytes) → torchvision-semantics RandomResizedCrop /
resize+center-crop → flip → normalize (float32 wire) or quantize (uint8
wire), fanned over a thread pool in native code (no GIL, no per-sample
Python) and written straight into a batch buffer of the wire's own dtype.
Falls back to the pure-Python pipeline
automatically when the library can't be built or a file is an unsupported
format.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional, Tuple

import numpy as np

from .transforms import IMAGENET_MEAN, IMAGENET_STD

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "dataplane.cpp")
_LIB_DIR = os.path.join(_REPO_ROOT, "native", "build")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]
# libpng is optional: on hosts without it, fall back to a JPEG-only build
# (-DDP_NO_PNG) rather than losing the whole native path — PNGs then take
# the per-slot PIL retry, JPEGs stay native.
_LINK_VARIANTS = (["-ljpeg", "-lpng", "-lpthread"],
                  ["-DDP_NO_PNG", "-ljpeg", "-lpthread"])

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
# why the native path is off (the compiler's / loader's own message);
# "" while it is on or untried. The trainer banner and chip_smoke.py print it.
build_error = ""


def _lib_path(extra) -> str:
    """The built library is keyed by a hash of the source AND the flags: a
    binary left over from another source (a copied tree keeps no mtimes) is
    simply never looked at."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX + list(extra)).encode())
    return os.path.join(_LIB_DIR, f"libdataplane.{h.hexdigest()[:16]}.so")


def _build(target: str, extra) -> str:
    """Compile to `target` (atomically, through a temp file of this
    process's own, so concurrent builders only ever race on the final
    rename of identical bytes); returns "" or the failure text."""
    tmp = ""
    try:
        os.makedirs(_LIB_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=_LIB_DIR, suffix=".so.tmp")
        os.close(fd)
        subprocess.run(_CXX + ["-o", tmp, _SRC] + list(extra), check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, target)
        return ""
    except subprocess.CalledProcessError as e:
        return (e.stderr or b"").decode(errors="replace").strip()[-2000:]
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    finally:
        if tmp and os.path.exists(tmp):
            os.unlink(tmp)


def _load(path: str) -> ctypes.CDLL:
    lib = ctypes.CDLL(path)
    lib.dp_has_png.restype = ctypes.c_int
    lib.dp_has_png.argtypes = []
    lib.dp_load_batch.restype = ctypes.c_int
    lib.dp_load_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_double,
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int,
    ]
    lib.dp_load_batch_u8.restype = ctypes.c_int
    lib.dp_load_batch_u8.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_double,
        ctypes.c_uint64, ctypes.c_int,
    ]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building on first use) the native dataplane, or None — then
    `build_error` says why."""
    global _lib, _load_failed, build_error
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        errors = []
        for extra in _LINK_VARIANTS:
            path = _lib_path(extra)
            err = "" if os.path.exists(path) else _build(path, extra)
            if not err:
                try:
                    _lib = _load(path)
                    return _lib
                except (OSError, AttributeError) as e:
                    err = f"{type(e).__name__}: {e}"
            errors.append(f"[{' '.join(extra)}] {err}")
        build_error = " ; ".join(errors)
        _load_failed = True
        return None


_MEAN = (ctypes.c_float * 3)(*IMAGENET_MEAN)
_STD = (ctypes.c_float * 3)(*IMAGENET_STD)


def native_decodes_png() -> bool:
    """True when the loaded dataplane build includes libpng (False for the
    JPEG-only -DDP_NO_PNG fallback, where PNGs take the per-slot PIL
    retry)."""
    lib = get_lib()
    return bool(lib is not None and lib.dp_has_png())


def native_load_batch(
    paths,
    out_size: int,
    train: bool,
    resize_short: int = 256,
    scale: Tuple[float, float] = (0.8, 1.0),
    seed: int = 0,
    num_threads: int = 4,
    out_dtype: str = "float32",
) -> Optional[Tuple[np.ndarray, int]]:
    """Decode+transform a list of JPEG/PNG paths into a (B, S, S, 3) batch
    of `out_dtype`: "float32" (ImageNet-normalized) or "uint8" (the 0..255
    pixels, rounded half-to-even and clamped inside the C workers — the
    uint8 wire; the jitted step normalizes on device). Both come from one
    resample kernel: the uint8 batch is the quantized float one, byte for
    byte, without the float one ever existing.

    Returns (batch, n_failures) or None when the native library is
    unavailable. Failure slots are zero-filled; the caller patches them via
    the Python path.
    """
    lib = get_lib()
    if lib is None:
        return None
    if out_dtype == "uint8":
        ctype, call, norm = ctypes.c_uint8, lib.dp_load_batch_u8, ()
    elif out_dtype == "float32":
        ctype, call, norm = ctypes.c_float, lib.dp_load_batch, (_MEAN, _STD)
    else:
        raise ValueError(f"unknown native out_dtype {out_dtype!r}")
    n = len(paths)
    out = np.empty((n, out_size, out_size, 3), out_dtype)
    arr = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
    errors = call(
        arr, n, out.ctypes.data_as(ctypes.POINTER(ctype)),
        out_size, out_size, int(train), resize_short,
        float(scale[0]), float(scale[1]), ctypes.c_uint64(seed),
        *norm, num_threads,
    )
    return out, int(errors)


class NativeBatcher:
    """Batch assembler for `ShardedLoader(batcher=...)` over a path-based
    dataset (ImageFolderDataset). One native call per batch; slots the C side
    could not decode (unsupported format/corrupt) are re-loaded through the
    dataset's PIL transform, so behavior is identical up to resampling
    details."""

    # native path covers these presets (RRC+flip / resize+center-crop);
    # 'cdr' (rotation) and 'cifar' (pad+crop on raw 32px) stay in Python
    SUPPORTED = ("baseline", "clothing1m")

    def __init__(self, dataset, preset: str, train: bool,
                 image_size: int, crop_size: int, seed: int, num_threads: int = 4,
                 out_dtype: str = "float32"):
        from .transforms import build_transform

        self.dataset = dataset
        self.train = train
        self.seed = seed
        self.num_threads = num_threads
        self.resize_short = crop_size
        # uint8 wire: the C workers quantize (round half-to-even, clamp) and
        # write the uint8 batch themselves; the jitted step normalizes on
        # device. The native train flip stays on (the C signature ties it
        # to `train`), so with the device epilogue's flip the sample is
        # flipped twice with independent draws — the composed distribution
        # is still flip-with-prob-0.5, augmentation-equivalent.
        self.out_dtype = out_dtype
        # which code fills the batch, as the loader's `input.load` spans and
        # `input_native_batches_total{wire}` name it (docs/observability.md)
        self.path = "native_u8" if out_dtype == "uint8" else "native_f32"
        # mirror build_transform's output-size quirk (train@crop_size for
        # baseline) AND its out_dtype validation
        t = build_transform(preset, train, image_size, crop_size,
                            out_dtype=out_dtype)
        self.out_size = t.out_size
        self.scale = (0.08, 1.0) if preset == "clothing1m" else (0.8, 1.0)

    @staticmethod
    def available() -> bool:
        return get_lib() is not None

    def __call__(self, indices: np.ndarray, epoch: int, batch_idx: int):
        paths = [self.dataset.paths[int(i)] for i in indices]
        labels = np.asarray(
            [self.dataset.labels[int(i)] for i in indices], np.int32)
        seed = (self.seed * 1_000_003 + epoch * 10_007 + batch_idx) & 0xFFFFFFFF
        res = native_load_batch(
            paths, self.out_size, self.train, self.resize_short,
            self.scale, seed, self.num_threads, out_dtype=self.out_dtype)
        if res is None:
            raise RuntimeError("native dataplane unavailable")
        images, errors = res
        if errors:
            # zero-filled slots → the dataset's PIL transform, which yields
            # the same wire dtype (PIL quantizes at the same point; within
            # the documented "up to resampling details" envelope)
            rng = np.random.default_rng(seed)
            for j in np.nonzero(~images.reshape(len(images), -1).any(axis=1))[0]:
                img, _ = self.dataset.__getitem__(int(indices[j]), rng)
                images[j] = img
        return images, labels
