"""`folder` generator: a JPEG image folder on disk, decoded and cropped on
the host by the program's native dataplane and staged by its
DevicePrefetcher — what `cli.train --dataset imagefolder` users run.

The folder is a fixed data set (as ImageNet is): made once per checkout
under the benchmark's cache directory from the mix's own `data_seed`, and
read from the page cache after. `--seed` changes the order in which the
files are drawn (the program's crop and flip streams stay on its own default
seed). So every seed decodes the same files, in another order.

Parameters (the mix's .json): files, classes, width, height, quality,
data_seed, epoch_steps. The generator of the pixels is copied from
`bench_input.ensure_dataset` (smooth low-frequency content plus noise:
a realistic decode cost and file size).
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _write_class(args) -> None:
    root, c, per_class, w, h, quality, data_seed = args
    from PIL import Image

    rng = np.random.default_rng((data_seed, c))
    d = os.path.join(root, f"class{c:03d}")
    os.makedirs(d, exist_ok=True)
    for i in range(per_class):
        low = rng.integers(0, 255, (max(h // 16, 1), max(w // 16, 1), 3), np.uint8)
        img = Image.fromarray(low).resize((w, h), Image.BILINEAR)
        arr = np.asarray(img, np.int16) + rng.integers(-12, 12, (h, w, 3), np.int16)
        Image.fromarray(np.clip(arr, 0, 255).astype(np.uint8)).save(
            os.path.join(d, f"img{i:05d}.jpg"), quality=quality)


def ensure_folder(root: str, p: dict) -> str:
    """The folder for these parameters, generating it if its stamp is
    missing. A half-written folder has no stamp and is written over."""
    stamp = "x".join(str(p[k]) for k in
                     ("files", "classes", "width", "height", "quality", "data_seed"))
    root = os.path.join(root, "folder_" + stamp)
    done = os.path.join(root, "complete")
    if os.path.exists(done):
        return root
    per_class = p["files"] // p["classes"]
    jobs = [(os.path.join(root, "train"), c, per_class, p["width"], p["height"],
             p["quality"], p["data_seed"]) for c in range(p["classes"])]
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(_write_class, jobs))
    with open(done, "w") as f:
        f.write(stamp)
    return root


def _params(params: dict, rehearse: bool) -> dict:
    return {**params, **params.get("rehearse", {})} if rehearse else params


def argv(params: dict, cache_dir: str, rehearse: bool) -> list:
    root = ensure_folder(cache_dir, _params(params, rehearse))
    train = os.path.join(root, "train")
    return ["--dataset", "imagefolder", "--train_dir", train, "--val_dir", train]


def datasets(params: dict, cfg, seed: int, batch: int, rehearse: bool):
    """The program's own ImageFolderDataset pair, the train side's file
    list repeated so that one epoch outlasts any window."""
    from ddp_classification_pytorch_tpu.train.loop import build_datasets

    p = _params(params, rehearse)
    train, val = build_datasets(cfg)
    # the run's seed orders the files (the program's loader then shuffles
    # with its own, fixed, seed): every seed the same files, in another order
    order = np.random.default_rng((seed, 0xF01D)).permutation(len(train.paths))
    reps = -(-batch * p["epoch_steps"] // len(order))
    train = dataclasses.replace(
        train, paths=[train.paths[i] for i in order] * reps,
        labels=np.tile(np.asarray(train.labels, np.int32)[order], reps))
    return train, val
