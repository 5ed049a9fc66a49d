"""`serve` entrypoint — stand up the micro-batching inference engine over a
trained checkpoint (serve/engine.py; runbook: docs/serving.md).

    python -m ddp_classification_pytorch_tpu.cli.serve baseline \
        --model resnet50 --num_classes 2173 --watch runs/baseline \
        --port 8000 --buckets 2,4,16 --batch_timeout_ms 5

Discipline shared with `cli/train.py`:

- deterministic config errors (bad buckets, topk > classes, a corrupt
  `--ckpt`, construction-time ValueErrors) exit **rc 2** before/without
  touching the backend — supervisors must not replay them;
- **SIGTERM/SIGINT drain gracefully**: intake stops, every already-queued
  request is answered, metrics print one final line, exit **rc 0** — the
  preemption-safe shutdown a supervisor can always send.

`--selfcheck N` serves N synthetic requests through the full engine path
(warmup → batcher thread → drain) and exits — the socket-free smoke the
tier-1 tests and fresh deployments use.
"""

from __future__ import annotations

import argparse
import os
import signal
import threading
from typing import Optional, Sequence

from ..config import Config, get_preset


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ddp_classification_pytorch_tpu.cli.serve",
        description="micro-batched inference serving over a trained checkpoint",
    )
    p.add_argument("workload", choices=["baseline", "arcface", "cdr", "nested", "plc"],
                   help="preset whose model/head the checkpoint was trained "
                        "with (same presets as cli.train)")

    m = p.add_argument_group("model")
    m.add_argument("--model", "--arch", dest="model", default="",
                   help="resnet18/34/50/101/152 | vgg19_bn | tresnet_m | "
                        "vit_t16/s16/b16 (must match the checkpoint)")
    m.add_argument("--variant", default="", help="imagenet | cifar stem")
    m.add_argument("--dtype", default="", help="bfloat16 | float32 compute dtype")
    m.add_argument("--num_classes", type=int, default=0)
    m.add_argument("--image_size", type=int, default=0)
    m.add_argument("--input_dtype", default="", choices=["", "uint8", "float32"],
                   help="request wire format (default uint8: raw pixels, "
                        "normalization fused into the jitted predict — same "
                        "dataplane as training)")

    s = p.add_argument_group("serving")
    s.add_argument("--ckpt", default="",
                   help="explicit checkpoint to serve (sha256-verified; a "
                        "corrupt file is a deterministic rc 2)")
    s.add_argument("--watch", default="",
                   help="run dir to serve from AND poll for checkpoint "
                        "hot-reload (newest verified checkpoint wins; "
                        "corrupt candidates are quarantined, serving "
                        "continues on the previous params)")
    s.add_argument("--reload_poll_s", type=float, default=-1.0,
                   help="hot-reload poll cadence for --watch (default 5)")
    s.add_argument("--buckets", default="",
                   help="comma list of padded batch shapes, ascending "
                        "(e.g. 2,4,16); compile count == bucket count; every "
                        "bucket must be divisible by the serve mesh's dp "
                        "width (rc 2 otherwise). Default: powers of two up "
                        "to --max_batch, rounded up to the dp width")
    s.add_argument("--max_batch", type=int, default=0,
                   help="largest micro-batch the deadline batcher assembles "
                        "(default 8)")
    s.add_argument("--batch_timeout_ms", type=float, default=-1.0,
                   help="deadline from the first queued request until a "
                        "partial batch flushes (default 5; 0 = never wait)")
    s.add_argument("--queue_depth", type=int, default=0,
                   help="bounded intake queue; submits beyond it are "
                        "rejected (backpressure / HTTP 503; default 64)")
    s.add_argument("--topk", type=int, default=0,
                   help="classes returned per request (default 5)")
    s.add_argument("--port", type=int, default=-1,
                   help=">0: stdlib HTTP front-end (POST /predict, "
                        "GET /healthz|/metrics); default: engine only")
    s.add_argument("--selfcheck", type=int, default=0,
                   help="serve N synthetic requests through the full engine "
                        "path, print metrics, drain, exit 0 (smoke mode)")
    s.add_argument("--serve_devices", "--serve-devices", dest="serve_devices",
                   type=int, default=-1,
                   help="devices on the serve mesh's data axis (0 = all "
                        "visible, the default): padded bucket batches shard "
                        "over them, so throughput scales with the pod; "
                        "buckets must divide evenly (rc 2 otherwise)")
    s.add_argument("--aot_cache", "--aot-cache", dest="aot_cache", default="",
                   help="AOT executable sidecar: 'auto' (default) banks "
                        "compiled bucket programs in <ckpt dir>/aot so the "
                        "next replica boots without compiling, 'off' "
                        "disables, else an explicit sidecar dir")
    s.add_argument("--fleet_dir", "--fleet-dir", dest="fleet_dir", default="",
                   help="shared fleet run dir: replicas heartbeat via "
                        "<dir>/serve_fleet/lease.r<id> and serialize hot "
                        "reloads through one drain token (rolling wave, at "
                        "most one replica draining); default: lone replica")
    s.add_argument("--fleet_replica", "--fleet-replica", dest="fleet_replica",
                   type=int, default=-1,
                   help="this replica's id in the shared --fleet_dir "
                        "(lowest live id is the leader; default 0)")
    s.add_argument("--fleet_ttl_s", "--fleet-ttl-s", dest="fleet_ttl_s",
                   type=float, default=-1.0,
                   help="lease/drain-token freshness horizon: a lease older "
                        "than this is a dead replica, a stale token is "
                        "taken over so a kill mid-wave cannot wedge the "
                        "wave (default 15)")
    s.add_argument("--admission_deadline_ms", "--admission-deadline-ms",
                   dest="admission_deadline_ms", type=float, default=-1.0,
                   help=">0: shed requests when the MEASURED queue wait "
                        "(depth / observed service rate) exceeds this "
                        "deadline — fair-share tenants shed at 1x, any "
                        "tenant at 2x; 503 bodies carry the depth + shed "
                        "tenant (default 0 = engine queue bound only)")
    s.add_argument("--admission_tenants", "--admission-tenants",
                   dest="admission_tenants", default="",
                   help="per-tenant weighted fair shares for admission, "
                        "'name:weight,name:weight' (requests pick a tenant "
                        "via the X-Tenant header; default: one 'default' "
                        "tenant at weight 1)")
    s.add_argument("--strict_compile", action="store_true",
                   help="make a steady-state recompile fatal (rc 2): warmup "
                        "prepays exactly len(buckets) programs and arms a "
                        "compile sentinel; default logs + counts it in "
                        "metrics (analysis/compile_sentinel.py)")

    r = p.add_argument_group("run")
    r.add_argument("--out", default="", help="metrics/records output dir")
    r.add_argument("--tensorboard", action="store_true",
                   help="write serve/* scalar curves to <out>/tb")
    r.add_argument("--log_every_s", type=float, default=-1.0,
                   help="metrics console line cadence (default 10)")
    r.add_argument("--seed", type=int, default=-1)
    r.add_argument("--platform", default="", choices=["", "tpu", "cpu"],
                   help="force a JAX platform (as cli.train)")
    return p


def config_from_args(args: argparse.Namespace) -> Config:
    cfg = get_preset(args.workload)
    if args.model:
        cfg.model.arch = args.model
    if args.variant:
        cfg.model.variant = args.variant
    if args.dtype:
        cfg.model.dtype = args.dtype
    if args.num_classes:
        cfg.data.num_classes = args.num_classes
    if args.image_size:
        cfg.data.image_size = args.image_size
    if args.input_dtype:
        cfg.data.input_dtype = args.input_dtype
    if args.seed >= 0:
        cfg.run.seed = args.seed
    if args.out:
        cfg.run.out_dir = args.out
    if args.tensorboard:
        cfg.run.tensorboard = True

    sv = cfg.serve
    if args.ckpt:
        sv.checkpoint = args.ckpt
    if args.watch:
        sv.watch_dir = args.watch
    if args.reload_poll_s >= 0:
        sv.reload_poll_s = args.reload_poll_s
    if args.buckets:
        sv.buckets = tuple(int(b) for b in args.buckets.split(",") if b)
    if args.max_batch:
        sv.max_batch = args.max_batch
    if args.batch_timeout_ms >= 0:
        sv.batch_timeout_ms = args.batch_timeout_ms
    if args.queue_depth:
        sv.queue_depth = args.queue_depth
    if args.topk:
        sv.topk = args.topk
    if args.port >= 0:
        sv.port = args.port
    if args.log_every_s >= 0:
        sv.log_every_s = args.log_every_s
    if args.strict_compile:
        sv.strict_compile = True
    if args.serve_devices >= 0:
        sv.serve_devices = args.serve_devices
    if args.aot_cache:
        sv.aot_cache = args.aot_cache
    if args.fleet_dir:
        sv.fleet_dir = args.fleet_dir
    if args.fleet_replica >= 0:
        sv.fleet_replica = args.fleet_replica
    if args.fleet_ttl_s >= 0:
        sv.fleet_ttl_s = args.fleet_ttl_s
    if args.admission_deadline_ms >= 0:
        sv.admission_deadline_ms = args.admission_deadline_ms
    if args.admission_tenants:
        sv.admission_tenants = args.admission_tenants

    # dp divisibility re-resolves against the real mesh width in main()
    # (inside the same rc-2 net); this catches the dp-independent errors
    # before any backend work
    sv.resolve_buckets()  # raises ValueError on bad knob combinations
    sv.validate_fleet()  # fleet/admission knobs are config-shaped too
    if sv.topk > cfg.data.num_classes:
        raise ValueError(
            f"serve.topk={sv.topk} exceeds num_classes={cfg.data.num_classes}")
    if sv.checkpoint and sv.watch_dir:
        raise ValueError("--ckpt and --watch are mutually exclusive: an "
                         "explicit checkpoint pins the params, a watch dir "
                         "hot-reloads them")
    if not (sv.checkpoint or sv.watch_dir or args.selfcheck):
        raise ValueError("serving needs weights: pass --ckpt <file> or "
                         "--watch <run_dir> (or --selfcheck N to smoke the "
                         "engine on fresh params)")
    return cfg


def _resolve_aot_dir(cfg: Config) -> str:
    """Where the AOT executable sidecar lives ("" = disabled). 'auto' puts
    it next to the weights — the one location every replica of a
    deployment shares — and disables itself for a weightless selfcheck
    (fresh params have no durable identity worth keying a cache on)."""
    mode = cfg.serve.aot_cache
    if mode == "off":
        return ""
    if mode and mode != "auto":
        return mode
    if cfg.serve.checkpoint:
        base = os.path.dirname(os.path.abspath(cfg.serve.checkpoint)) or "."
        return os.path.join(base, "aot")
    if cfg.serve.watch_dir:
        return os.path.join(cfg.serve.watch_dir, "aot")
    return ""


def _install_signal_handlers(stop: threading.Event):
    """SIGTERM/SIGINT → set the drain event (the serve loop does the actual
    drain: stop intake, flush queue, exit rc 0). Returns the previous
    handlers so tests can restore them."""
    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, lambda *_: stop.set())
    return prev


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    try:
        # same pre-backend rc-2 discipline as cli.train: a bad knob combo
        # surfaces in milliseconds with the deterministic code supervisors
        # must not retry
        cfg = config_from_args(args)
    except ValueError as e:
        import sys

        print(f"[serve] config error: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from ..utils.cache import compile_stats_line, enable_persistent_cache

    enable_persistent_cache()

    import numpy as np

    from ..data.transforms import build_transform, preset_for_dataset
    from ..parallel import mesh as meshlib
    from ..serve.engine import ServingEngine
    from ..serve.metrics import ServeMetrics
    from ..serve.reload import CheckpointWatcher
    from ..train.checkpoint import CheckpointManager
    from ..train.state import create_train_state
    from ..train.steps import make_topk_predict_step
    from ..utils.logging import device_memory_line, host0_print

    try:
        # serving is pure DP: --serve_devices devices (0 = all) on 'data'.
        # Built inside the rc-2 net: an over-wide request or a dp-indivisible
        # explicit bucket is config-shaped, not a crash
        mesh = meshlib.serve_mesh(cfg.serve.serve_devices)
        dp = int(mesh.shape[meshlib.DATA_AXIS])
        cfg.serve.resolve_buckets(dp)
        model, _, state = create_train_state(cfg, mesh, steps_per_epoch=1)
        if cfg.serve.checkpoint:
            # explicit checkpoint: verification failure raises ValueError —
            # deterministic, so it maps to rc 2 like --resume in cli.train
            mgr = CheckpointManager(
                os.path.dirname(os.path.abspath(cfg.serve.checkpoint)) or ".",
                save_every_epoch=False, async_save=False)
            state = mgr.restore(state, cfg.serve.checkpoint)
            host0_print(f"[serve] serving {cfg.serve.checkpoint}")
    except ValueError as e:
        import sys
        import traceback

        # construction-time ValueErrors (unknown arch/head, corrupt --ckpt,
        # shape mismatches) are config-shaped → rc 2, same as cli.train
        traceback.print_exc(file=sys.stderr)
        print(f"[serve] config error: {e}", file=sys.stderr)
        raise SystemExit(2) from None

    predict = make_topk_predict_step(cfg, model, cfg.serve.topk, mesh=mesh)
    metrics = ServeMetrics()
    preset = preset_for_dataset(cfg.data.dataset, cfg.data.transform)
    transform = (build_transform(preset, train=False,
                                 image_size=cfg.data.image_size,
                                 crop_size=cfg.data.train_crop_size,
                                 out_dtype=cfg.data.input_dtype)
                 if preset is not None else None)
    aot_dir = _resolve_aot_dir(cfg)
    engine = ServingEngine.from_config(cfg, state, predict, metrics=metrics,
                                       transform=transform,
                                       mesh=mesh, aot_dir=aot_dir)

    fleet = None
    if cfg.serve.fleet_dir:
        from ..serve.fleet import FleetMember

        # shares the engine registry so fleet_* gauges ride /metrics; the
        # lease heartbeat itself piggybacks on the watcher poll tick
        fleet = FleetMember(cfg.serve.fleet_dir, cfg.serve.fleet_replica,
                            ttl_s=cfg.serve.fleet_ttl_s,
                            registry=metrics.registry)
    admission = None
    if cfg.serve.admission_deadline_ms > 0:
        from ..serve.fleet import AdmissionController

        admission = AdmissionController(
            engine, tenants=cfg.serve.admission_tenants,
            deadline_ms=cfg.serve.admission_deadline_ms,
            registry=metrics.registry)

    watcher = None
    if cfg.serve.watch_dir:
        from ..utils import chaos as chaoslib

        # watcher_io drills aim CHAOS_FAULT_SPEC at a replica; one-shot
        # markers live under this replica's own out dir, not the shared
        # watch dir (each replica owns its poll counter)
        plan = chaoslib.plan_for_run("", cfg.run.out_dir or ".", 0)
        watcher = CheckpointWatcher(cfg.serve.watch_dir, engine, state,
                                    poll_s=cfg.serve.reload_poll_s,
                                    metrics=metrics,
                                    chaos=plan if plan else None,
                                    fleet=fleet)
        loaded = watcher.restore_initial()
        host0_print(f"[serve] watching {cfg.serve.watch_dir} "
                    + (f"(serving epoch {loaded})" if loaded >= 0 else
                       "(no verified checkpoint yet; serving fresh params "
                       "until one lands)"))

    dev0 = jax.devices()[0]
    host0_print(f"[serve] arch={cfg.model.arch} classes={cfg.data.num_classes} "
                f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
                f"devices={len(jax.devices())} "
                f"wire={cfg.data.input_dtype} buckets={list(engine.buckets)} "
                f"max_batch={cfg.serve.max_batch} "
                f"timeout={cfg.serve.batch_timeout_ms}ms "
                f"topk={cfg.serve.topk} serve_devices={engine.serve_devices} "
                f"dp={engine.dp} aot={aot_dir or 'off'}")
    engine.warmup()  # ready every bucket executable before traffic
    host0_print(
        f"[serve] warm boot: {len(engine.buckets)} bucket executables "
        "AOT-deserialized, zero compiles" if engine.aot_hit else
        f"[serve] cold boot: {len(engine.buckets)} bucket programs compiled"
        + (" (banked to AOT sidecar)" if aot_dir else ""))

    tb = None
    if cfg.run.tensorboard:
        from ..utils.tensorboard import SummaryWriter

        tb = SummaryWriter(os.path.join(cfg.run.out_dir, "tb"), "serve")

    if args.selfcheck:
        engine.start()
        rng = np.random.default_rng(cfg.run.seed)
        h = cfg.data.image_size
        imgs = (rng.integers(0, 256, (args.selfcheck, h, h, 3)).astype(np.uint8)
                if cfg.data.input_dtype == "uint8"
                else rng.normal(size=(args.selfcheck, h, h, 3)).astype(np.float32))
        futures = [engine.submit(img) for img in imgs]
        for f in futures:
            f.result(timeout=120)
        engine.drain()
        if watcher is not None:
            watcher.stop()
        if fleet is not None:
            fleet.leave()
        host0_print(metrics.log_line(engine.queue_depth))
        if tb is not None:
            metrics.to_tensorboard(tb, 0)
            tb.close()
        if engine.fatal_error is not None:
            import sys

            # strict_compile tripped: deterministic (the same traffic
            # replays the same cache miss) → rc 2, do not restart
            print(f"[serve] {engine.fatal_error}", file=sys.stderr)
            raise SystemExit(2)
        host0_print(f"[serve] {compile_stats_line()}")
        host0_print(f"[serve] {device_memory_line()}")
        host0_print(f"[serve] selfcheck ok: {args.selfcheck} requests, "
                    f"buckets used {sorted(engine.seen_buckets)}")
        return

    stop = threading.Event()
    _install_signal_handlers(stop)
    engine.start()
    if watcher is not None:
        watcher.start()
    server = None
    if cfg.serve.port:
        from ..serve.http import start_server

        server = start_server(engine, cfg.serve.port, watcher=watcher,
                              fleet=fleet, admission=admission)
        host0_print(f"[serve] http on :{cfg.serve.port} "
                    "(POST /predict, GET /healthz, GET /metrics)")
    if fleet is not None and watcher is None:
        # --ckpt pins the params (no watcher poll to ride): announce the
        # pinned digest once so the registry sees this replica at all
        fleet.heartbeat(digest=engine.params_digest,
                        generation=engine.params_generation)
    from ..obs.events import emit

    emit("serve_ready", port=cfg.serve.port,
         epoch=(watcher.loaded_epoch if watcher is not None else -1))

    step = 0
    while not stop.wait(cfg.serve.log_every_s):
        if engine.fatal_error is not None:
            break  # strict_compile tripped: intake already stopped
        host0_print(metrics.log_line(engine.queue_depth))
        if tb is not None:
            metrics.to_tensorboard(tb, step)
            tb.flush()
        step += 1

    # graceful drain: intake stops first (HTTP answers 503), then every
    # already-accepted request is served, then exit 0
    host0_print("[serve] SIGTERM/SIGINT: draining — intake stopped, "
                f"{engine.queue_depth} request(s) queued")
    emit("drain_begin", queued=engine.queue_depth)
    if server is not None:
        server.shutdown()
    if watcher is not None:
        watcher.stop()
    engine.drain()
    if fleet is not None:
        fleet.leave()  # drop the lease now, not after the TTL
    emit("drain_end")
    host0_print(metrics.log_line(engine.queue_depth))
    if tb is not None:
        metrics.to_tensorboard(tb, step)
        tb.close()
    if engine.fatal_error is not None:
        import sys

        print(f"[serve] {engine.fatal_error}", file=sys.stderr)
        raise SystemExit(2)
    host0_print("[serve] drained clean")


if __name__ == "__main__":
    main()
