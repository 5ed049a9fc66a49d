"""ViT perf A/B at bench shapes (the lowest-utilization bench row).

Measures the vit_s16 train step under one-change-at-a-time variants,
with bench.py's own row machinery (same AOT compile, median-of-chunks
timing, MFU + roofline fields), so numbers are directly comparable to
bench.py's own rows:

    baseline    — the bench's auto-pick configuration (dense at 196 tok)
    ln_bf16     — LayerNorms in bf16 instead of f32 (bandwidth lever)
    remat_dots  — per-block checkpoint with the checkpoint_dots policy
                  (memory lever; expected slower — measured to document)
    flash       — force the Pallas kernel below its auto-pick floor
                  (re-check of the dense-vs-flash A/B at 196 tokens)

Has never run on the chip (ROADMAP S3):

    python scripts/ab_vit_perf.py [--steps 30] [--batch 0]

One JSON line per variant; paste the verdict into docs/performance.md
(the ViT section) and flip ModelConfig.ln_bf16's default only on a
measured win + a convergence re-record.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import bench  # noqa: E402  (repo root — reuse rows, peak tables)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--batch", type=int, default=0, help="0 = 128/chip")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--variants", default="baseline,ln_bf16,remat_dots,flash")
    args = ap.parse_args()

    from ddp_classification_pytorch_tpu.utils.cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()

    import jax

    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    devices = jax.devices()
    n_chips = len(devices)
    on_accel = devices[0].platform in ("tpu", "gpu")
    peak = (bench._peak_flops(devices[0].device_kind)
            if devices[0].platform == "tpu" else None)
    peak_bw = (bench._peak_hbm(devices[0].device_kind)
               if devices[0].platform == "tpu" else None)
    mesh = meshlib.make_mesh(devices=devices)

    def cfg_for(variant: str):
        c = get_preset("baseline")
        c.model.arch = "vit_s16"
        c.model.dtype = "bfloat16" if on_accel else "float32"
        c.model.flash_attention = True  # bench auto-pick parity
        c.data.num_classes = 1000
        c.data.image_size = args.image_size if on_accel else 64
        c.data.batch_size = args.batch or (128 if on_accel else 8) * n_chips
        if variant == "ln_bf16":
            c.model.ln_bf16 = True
        elif variant == "remat_dots":
            c.model.remat = True
        elif variant == "flash":
            c.model.flash_min_tokens = 0  # kernel even at 196 tokens
        elif variant != "baseline":
            raise SystemExit(f"unknown variant {variant!r}")
        return c

    steps = args.steps if on_accel else 2
    warmup = args.warmup if on_accel else 1
    for variant in [v for v in args.variants.split(",") if v]:
        t0 = time.monotonic()
        row = bench._bench_row(
            cfg_for(variant), mesh, steps=steps, warmup=warmup,
            n_chips=n_chips, peak=peak, peak_bw=peak_bw,
            metric=f"vit_s16_{variant}_train_images_per_sec_per_chip")
        row["variant"] = variant
        print(json.dumps(row), flush=True)
        print(f"# {variant}: {row['value']} img/s/chip, "
              f"step {row['step_ms']}ms, mfu {row.get('mfu', 'n/a')}, "
              f"{time.monotonic() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
