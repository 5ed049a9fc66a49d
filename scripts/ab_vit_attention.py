"""A/B the ViT attention paths at a given token count on the live backend.

Settles VERDICT r2 weak #3 / next-round #4 with a measurement: time the
full vit train step with (a) the Pallas flash kernel forced
(--flash_min_tokens 0) and (b) the XLA fused dense path, at the bench's
token count (224px → 196 tokens) and optionally a sweep, then print one
JSON line per point. The bench's auto-pick floor
(ModelConfig.flash_min_tokens) should sit below the measured crossover.

Usage: python scripts/ab_vit_attention.py [--sizes 224,448,736]
       [--batch 128] [--steps 30] [--arch vit_s16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vit_s16")
    ap.add_argument("--sizes", default="224,448",
                    help="comma list of image sizes (tokens = (S/16)^2)")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--platform", default="", choices=["", "tpu", "cpu"],
                    help="force a JAX platform (same contract as "
                         "cli/train.py)")
    args = ap.parse_args()

    from ddp_classification_pytorch_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    if args.platform:
        # same contract as cli/train.py: an explicit flag pins the platform
        # regardless of JAX_PLATFORMS in the env
        import jax

        jax.config.update("jax_platforms", args.platform)

    import jax
    import numpy as np

    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    devices = jax.devices()
    on_accel = devices[0].platform in ("tpu", "gpu")
    if not on_accel and args.platform != "cpu":
        # with no chip JAX falls to the CPU backend; full-size vit_s16
        # steps would grind for hours and the numbers would not answer the
        # TPU flash-vs-dense question anyway
        raise SystemExit(
            "backend is CPU but --platform cpu was not requested — refusing "
            "to measure the TPU crossover on the host (pass --platform cpu "
            "with small --sizes/--batch for a smoke run)")
    mesh = meshlib.make_mesh(devices=devices)

    for size in [int(s) for s in args.sizes.split(",") if s]:
        tokens = (size // 16) ** 2
        for mode, floor in (("flash", 0), ("dense", 10 ** 9)):
            cfg = get_preset("baseline")
            cfg.model.arch = args.arch
            cfg.model.flash_attention = True
            cfg.model.flash_min_tokens = floor
            cfg.model.dtype = "bfloat16" if on_accel else "float32"
            cfg.data.num_classes = 1000
            cfg.data.image_size = size
            cfg.data.batch_size = args.batch * len(devices)
            with mesh:
                model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=100)
                step = make_train_step(cfg, model, tx, mesh=mesh)
                rng = np.random.default_rng(0)
                images = jax.device_put(
                    rng.normal(size=(cfg.data.batch_size, size, size, 3))
                    .astype(np.float32), meshlib.batch_sharding(mesh))
                labels = jax.device_put(
                    rng.integers(0, 1000, cfg.data.batch_size).astype(np.int32),
                    meshlib.batch_sharding(mesh))
                compiled = step.lower(state, images, labels).compile()
                for _ in range(args.warmup):
                    state, m = compiled(state, images, labels)
                if args.warmup:
                    float(m["loss"])  # hard sync
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    state, m = compiled(state, images, labels)
                float(m["loss"])
                dt = (time.perf_counter() - t0) / args.steps
            print(json.dumps({
                "metric": f"{args.arch}_{mode}_step_ms",
                "tokens": tokens,
                "image_size": size,
                "batch_per_chip": args.batch,
                "value": round(dt * 1e3, 2),
                "images_per_sec_per_chip": round(
                    cfg.data.batch_size / dt / len(devices), 1),
            }), flush=True)


if __name__ == "__main__":
    main()
