"""Step-time breakdown probe for the flagship train step.

This probe decomposes the step without a profiler trace, by timing nested
subgraphs of the SAME jitted computation:

  fwd        model.apply only (loss, no grad)
  fwd+bwd    value_and_grad, discard updates
  full step  value_and_grad + optimizer update (the bench's step)

and audits the compiled HLO for dtype leaks (f32 convolutions/dots that
should be bf16) plus reports XLA's per-execution FLOPs and peak HBM usage.

Usage: python scripts/perf_probe.py [--batch 256] [--image-size 224]
       [--arch resnet50] [--steps 30] [--remat] [--sweep 64,128,256,512]
"""

from __future__ import annotations

import argparse
import re
import sys
import time


def _time_compiled(compiled, args, steps: int, sync) -> float:
    out = None
    for _ in range(3):  # warmup
        out = compiled(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps


def _time_full_step(compiled, state, images, labels, steps: int) -> float:
    """Steady-state seconds/step for the donated train step: the output state
    feeds back in, so donation is satisfied on every iteration; a metric
    device-get closes each timing window."""
    out_state = state
    for _ in range(3):
        out_state, m = compiled(out_state, images, labels)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        out_state, m = compiled(out_state, images, labels)
    float(m["loss"])
    return (time.perf_counter() - t0) / steps


def _hlo_dtype_audit(compiled) -> dict:
    """Count convolution/dot ops by result dtype in the optimized HLO."""
    try:
        hlo = compiled.as_text()
    except Exception:
        return {}
    counts: dict = {}
    # optimized-HLO form: `%name = bf16[256,56,56,256]{layout} convolution(...)`
    for m in re.finditer(r"= (\w+)\[[^\]]*\](?:\{[^}]*\})? (convolution|dot)\(", hlo):
        key = f"{m.group(2)}_{m.group(1)}"
        counts[key] = counts.get(key, 0) + 1
    return counts


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--sweep", default="",
                    help="comma batch list: time the FULL step at each")
    args = ap.parse_args()

    from ddp_classification_pytorch_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    devices = jax.devices()
    on_accel = devices[0].platform in ("tpu", "gpu")
    if not on_accel:
        # a TPU-lease outage can answer the probe with the CPU backend; the
        # 224px/batch-256 defaults would then grind for hours — downsize to
        # a smoke-scale run instead (the numbers are only meaningful on TPU)
        print("# non-accelerator backend: downsizing to smoke scale",
              file=sys.stderr)
        args.batch, args.image_size = min(args.batch, 16), min(args.image_size, 64)
        args.steps, args.sweep = min(args.steps, 3), ""
    mesh = meshlib.make_mesh(devices=devices)

    def build(batch):
        cfg = get_preset("baseline")
        cfg.model.arch = args.arch
        cfg.model.dtype = "bfloat16" if on_accel else "float32"
        cfg.model.remat = args.remat
        cfg.data.num_classes = 1000
        cfg.data.image_size = args.image_size
        cfg.data.batch_size = batch
        with mesh:
            model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=100)
        rng = np.random.default_rng(0)
        h = cfg.data.image_size
        images = jax.device_put(
            rng.normal(size=(batch, h, h, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh))
        labels = jax.device_put(
            rng.integers(0, 1000, batch).astype(np.int32),
            meshlib.batch_sharding(mesh))
        return cfg, model, tx, state, images, labels

    def sync_tree(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(jax.device_get(leaf.ravel()[0] if leaf.ndim else leaf))

    cfg, model, tx, state, images, labels = build(args.batch)

    def loss_only(params, batch_stats, images, labels):
        variables = {"params": params, "batch_stats": batch_stats}
        logits, _ = model.apply(variables, images, train=True,
                                mutable=["batch_stats"],
                                rngs={"dropout": jax.random.PRNGKey(0)})
        import optax
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()

    def grad_only(params, batch_stats, images, labels):
        g = jax.grad(loss_only)(params, batch_stats, images, labels)
        return jax.tree_util.tree_reduce(
            lambda a, x: a + x.astype(jnp.float32).sum(), g, 0.0)

    with mesh:
        print(f"# probe: {args.arch} batch {args.batch} {args.image_size}px "
              f"remat={args.remat} on {devices[0].device_kind} x{len(devices)}",
              file=sys.stderr)

        fwd = jax.jit(loss_only).lower(
            state.params, state.batch_stats, images, labels).compile()
        t_fwd = _time_compiled(
            fwd, (state.params, state.batch_stats, images, labels),
            args.steps, sync_tree)

        bwd = jax.jit(grad_only).lower(
            state.params, state.batch_stats, images, labels).compile()
        t_bwd = _time_compiled(
            bwd, (state.params, state.batch_stats, images, labels),
            args.steps, sync_tree)

        step = make_train_step(cfg, model, tx, mesh=mesh)
        full = step.lower(state, images, labels).compile()
        audit = _hlo_dtype_audit(full)
        try:
            mem = full.memory_analysis()
            peak = getattr(mem, "peak_memory_in_bytes", None)
            if isinstance(mem, (list, tuple)):
                peak = getattr(mem[0], "peak_memory_in_bytes", None)
        except Exception:
            peak = None
        t_full = _time_full_step(full, state, images, labels, args.steps)

    b = args.batch
    print(f"fwd_only_ms        {t_fwd * 1e3:8.2f}   ({b / t_fwd:8.0f} img/s)")
    print(f"fwd_bwd_ms         {t_bwd * 1e3:8.2f}   ({b / t_bwd:8.0f} img/s)")
    print(f"full_step_ms       {t_full * 1e3:8.2f}   ({b / t_full:8.0f} img/s)")
    print(f"optimizer_overhead {max(t_full - t_bwd, 0.0) * 1e3:8.2f} ms")
    # t_bwd times the whole value_and_grad (forward AND backward); subtract
    # the forward so the ratio is backward/forward, not (f+b)/f
    print(f"bwd_over_fwd       {max(t_bwd - t_fwd, 0.0) / t_fwd:8.2f}x")
    if peak:
        print(f"peak_hbm_bytes     {peak:>12,}  ({peak / 2**30:.2f} GiB)")
    if audit:
        print("hlo_matmul_conv_dtypes:")
        for k, v in sorted(audit.items()):
            print(f"  {k:24s} {v}")

    for bs in [int(x) for x in args.sweep.split(",") if x]:
        if bs == args.batch:  # already measured above; compiles cost minutes
            print(f"sweep batch {bs:5d}: {t_full * 1e3:8.2f} ms/step  "
                  f"{bs / t_full:8.0f} img/s")
            continue
        try:
            cfg, model, tx, state, images, labels = build(bs)
            with mesh:
                step = make_train_step(cfg, model, tx, mesh=mesh)
                compiled = step.lower(state, images, labels).compile()
                t = _time_full_step(compiled, state, images, labels, args.steps)
            print(f"sweep batch {bs:5d}: {t * 1e3:8.2f} ms/step  "
                  f"{bs / t:8.0f} img/s")
        except Exception as e:
            print(f"sweep batch {bs:5d}: FAILED {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
