"""Benchmark harness — training-step throughput for the flagship and the
parallelism-pentad representatives.

Prints ONE JSON line (flagship ResNet-50 keys at top level, extra rows under
"extra"):

    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "step_ms": N, "mfu": N, "extra": [{...}, ...]}

The reference publishes no numbers (BASELINE.md); `vs_baseline` is therefore
computed against a documented stand-in: 2500 images/sec/chip, the
commonly-cited MLPerf-era ResNet-50 mixed-precision training throughput of a
single A100 — the hardware class of the reference's own runs
(BASELINE/train.sh uses 2 local GPUs). vs_baseline = value / 2500.

`mfu` is model-FLOPs utilization: XLA's own cost analysis of the compiled
train step (flops per execution) divided by (step time × per-chip peak bf16
FLOP/s for the detected TPU generation). It makes round-over-round perf
regressions visible in absolute terms, not just relative to the A100 stand-in.

Budget: the run tracks a global deadline (--deadline, default 900 s) and
extra rows only start while enough budget remains. Finding no TPU is a
non-zero exit, not a CPU row — unless JAX_PLATFORMS=cpu was set explicitly,
the tests' rehearsal (tiny shapes, `_cpu`-suffixed metric names: a count of
what ran, never a device number).

`--e2e` adds an end-to-end row (`<arch>_e2e_images_per_sec_per_chip`):
the real `ShardedLoader → DevicePrefetcher → train step` pipeline against
a generated on-disk image folder (synthetic on CPU), so host assembly +
H2D overlap — the stage the device-only rows exclude by design and
bench_input.py (host-only) cannot see — is a measured, regression-guarded
number (docs/performance.md "H2D overlap and the e2e benchmark"). The row
carries `h2d_bytes_per_step` + `input_dtype` evidence of the wire format
(`--input-dtype`, default uint8: raw pixels at ¼ the float32 bytes,
normalization fused into the jitted step — docs/performance.md "Wire
format: uint8 H2D").

Usage: python bench.py [--batch N] [--steps N] [--arch resnet50]
                       [--deadline SECONDS] [--rows arcface,vit] [--e2e]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

A100_RESNET50_IMG_PER_SEC = 2500.0

# Per-chip dense bf16 peak FLOP/s by device_kind substring (public specs).
# Matched longest-prefix-first so "TPU v5 lite" does not hit "TPU v5".
_PEAK_BF16 = (
    ("TPU v6 lite", 918e12),  # Trillium / v6e
    ("TPU v5 lite", 197e12),  # v5e
    ("TPU v5p", 459e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 46e12),
)

# Per-chip HBM bandwidth, bytes/s (public specs) — the roofline the
# flagship step is argued to sit at (docs/performance.md "Where the
# ceiling is"). Emitting achieved GB/s per row turns that argument into a
# measurement.
_PEAK_HBM = (
    ("TPU v6 lite", 1640e9),  # Trillium / v6e
    ("TPU v5 lite", 819e9),   # v5e
    ("TPU v5p", 2765e9),
    ("TPU v4", 1228e9),
    ("TPU v3", 900e9),
    ("TPU v2", 700e9),
)


def _lookup_peak(table, device_kind: str) -> float:
    """A device that is not in the table is an error, not a default."""
    for prefix, peak in table:
        if device_kind.startswith(prefix):
            return peak
    raise KeyError(f"no published peak for device_kind {device_kind!r}; "
                   "add it to bench.py's tables with its source")


def _peak_flops(device_kind: str) -> float:
    return _lookup_peak(_PEAK_BF16, device_kind)


def _peak_hbm(device_kind: str) -> float:
    return _lookup_peak(_PEAK_HBM, device_kind)


def _cost_of(compiled) -> tuple[float | None, float | None]:
    """(flops, bytes_accessed) PER DEVICE per execution from XLA's cost
    analysis (the analysis runs on the SPMD-partitioned module, so
    sharded-out work is already divided out); None when the backend does
    not report a counter. `bytes accessed` is XLA's post-fusion estimate
    of operand+output traffic — the standard roofline proxy (it assumes
    no inter-op cache reuse, so it slightly over-counts true HBM bytes)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        f = float(ca.get("flops", 0.0))
        b = float(ca.get("bytes accessed", 0.0))
        return (f if f > 0 else None), (b if b > 0 else None)
    except Exception:
        return None, None


def _flops_of(compiled) -> float | None:
    return _cost_of(compiled)[0]


def _phase_breakdown(cfg, mesh, model, state, images, labels, chunk_s,
                     trace_dir):
    """Per-step `{fwd, bwd, optimizer, collectives, h2d, idle}` ms.

    Two evidence sources, merged through ONE parser/schema (obs/trace.py):

    - **probes** — AOT sub-programs of the SAME production loss
      (train/steps.py::make_phase_probes): t(fwd) attributes the forward,
      t(fwd+bwd) − t(fwd) the backward, and the measured full step minus
      t(fwd+bwd) the optimizer. This is the only honest decomposition on
      backends whose trace op names carry no phase information (CPU
      XLA emits `dot.3` / `reduce-window`, not module scopes).
    - **the real capture** (when the profiler ran) — collectives and H2D
      transfer time, which the probes cannot see but whose trace names
      ARE unambiguous (`all-reduce`, `TransferToDevice`).

    The phases feed a SpanRecorder laid out inside each measured step
    window, so the emitted dict comes out of the same
    `parse_chrome_trace`/`aggregate` path a real on-device capture would
    use — idle is the unattributed remainder, and the six buckets sum to
    the measured step time by construction."""
    import jax

    from ddp_classification_pytorch_tpu.obs import trace as tracelib
    from ddp_classification_pytorch_tpu.train.steps import make_phase_probes

    def timed_s(compiled_fn, reps: int = 3) -> float:
        out = compiled_fn(state, images, labels)
        jax.tree_util.tree_map(float, out)  # hard sync past compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = compiled_fn(state, images, labels)
            jax.tree_util.tree_map(float, out)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    probes = make_phase_probes(cfg, model, mesh=mesh)
    fwd_s = timed_s(probes["fwd"].lower(state, images, labels).compile())
    fwd_bwd_s = timed_s(
        probes["fwd_bwd"].lower(state, images, labels).compile())
    bwd_s = max(fwd_bwd_s - fwd_s, 0.0)

    coll_s = h2d_s = 0.0
    source = "probes"
    if trace_dir is not None:
        real = tracelib.breakdown_from_trace_dir(trace_dir)
        if real:
            ragg = tracelib.aggregate(real)
            coll_s = ragg["collectives"] / 1e3
            h2d_s = ragg["h2d"] / 1e3
            source = "trace+probes"

    rec = tracelib.SpanRecorder()
    for i, step_s in enumerate(chunk_s):
        phases = {"fwd": fwd_s, "bwd": bwd_s,
                  "optimizer": max(step_s - fwd_bwd_s, 0.0)}
        if coll_s:
            phases["collectives"] = coll_s
        if h2d_s:
            phases["h2d"] = h2d_s
        rec.add_step(i, step_s, phases)
    return {"agg": tracelib.aggregate(rec.breakdown()), "source": source}


def _bench_row(cfg, mesh, *, steps: int, warmup: int, metric: str,
               n_chips: int, peak: float | None,
               peak_bw: float | None = None, seed: int = 0,
               trace: bool = False):
    """Compile (AOT, so cost analysis and execution share one compile),
    run warmup + timed steps on synthetic device-resident data, and return
    a row dict with images/sec/chip, step_ms and mfu. With `trace`, the
    timed window runs under jax.profiler and the row gains
    `step_breakdown_ms` +
    `breakdown_source` (see `_phase_breakdown`)."""
    import jax
    import numpy as np

    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    trace_dir = None
    tracing = False
    if trace:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")

    with mesh:
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=100)
        step = make_train_step(cfg, model, tx, mesh=mesh)

        rng = np.random.default_rng(seed)
        h = cfg.data.image_size
        batch = cfg.data.batch_size
        images = jax.device_put(
            rng.normal(size=(batch, h, h, 3)).astype(np.float32),
            meshlib.batch_sharding(mesh),
        )
        labels = jax.device_put(
            rng.integers(0, cfg.data.num_classes, batch).astype(np.int32),
            meshlib.batch_sharding(mesh),
        )

        compiled = step.lower(state, images, labels).compile()
        flops, bytes_accessed = _cost_of(compiled)

        # numerics evidence from the same compile window: the FLOP-weighted
        # bf16 fraction picks the MFU roofline's peak dtype, accum_dtype_ok
        # asserts the unwaivable contracts (dtype audit D1/D3/D4/D6)
        from ddp_classification_pytorch_tpu.analysis.dtype_audit import (
            step_dtype_evidence,
        )

        dtype_ev = step_dtype_evidence(step, (state, images, labels))

        for _ in range(warmup):
            state, metrics = compiled(state, images, labels)
        if warmup:
            float(metrics["loss"])  # device_get: hard sync

        # Median-of-chunks timing: one contiguous window folds a start-up
        # transient (the first row measured after backend init) into the
        # number; the median over 5 hard-synced chunks does not. 5 chunks
        # whenever steps allow: an odd count gives a single true median
        # element (an even count would need the middle pair's mean,
        # half-counting a transient chunk).
        n_chunks = min(5, max(steps // 5, 1))
        chunk_len = steps // n_chunks
        chunk_s = []
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        trace_step = 0
        try:
            for c in range(n_chunks):
                this_len = chunk_len + (steps % n_chunks if c == n_chunks - 1 else 0)
                t0 = time.perf_counter()
                for _ in range(this_len):
                    if tracing:
                        # the step marker obs/trace.py keys its windows on
                        with jax.profiler.StepTraceAnnotation(
                                "bench_step", step_num=trace_step):
                            state, metrics = compiled(state, images, labels)
                        trace_step += 1
                    else:
                        state, metrics = compiled(state, images, labels)
                float(metrics["loss"])  # hard sync closes the timing window
                chunk_s.append((time.perf_counter() - t0) / this_len)
        finally:
            if tracing:
                try:  # a leaked trace would keep profiling into later rows
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                tracing = False

        breakdown = None
        if trace:
            try:
                breakdown = _phase_breakdown(cfg, mesh, model, state, images,
                                             labels, chunk_s, trace_dir)
            except Exception as e:  # breakdown must not cost the row itself
                print(f"# step breakdown failed: {type(e).__name__}: {e}",
                      file=sys.stderr)

    chunk_s.sort()
    mid = len(chunk_s) // 2
    # true median: mean of the middle pair when the chunk count is even
    # (picking the upper-middle would systematically report the WORSE
    # chunk at n=2, reintroducing the transient this exists to absorb)
    step_s = (chunk_s[mid] if len(chunk_s) % 2
              else (chunk_s[mid - 1] + chunk_s[mid]) / 2)
    per_chip = batch / step_s / n_chips
    row = {
        "metric": metric,
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "step_ms": round(step_s * 1e3, 2),
        "step_ms_spread": [round(chunk_s[0] * 1e3, 2), round(chunk_s[-1] * 1e3, 2)],
    }
    row["bf16_op_fraction"] = dtype_ev["bf16_op_fraction"]
    row["accum_dtype_ok"] = dtype_ev["accum_dtype_ok"]
    if flops is not None and peak is not None:
        # flops is per-device (SPMD-partitioned module) → divide by the
        # per-chip peak only. `peak` is the bf16 MXU rate; when the
        # measured matmul work is predominantly f32 the honest roofline
        # denominator is half of it (f32 runs the MXU at half throughput) —
        # scoring an f32 run against the bf16 peak halves the reported MFU
        # and hides exactly the bf16-path gap the ≥0.45 target measures
        peak_dtype = ("bf16" if dtype_ev["bf16_op_fraction"] >= 0.5
                      else "f32")
        row["mfu"] = round(flops / step_s / (peak if peak_dtype == "bf16"
                                             else peak / 2), 4)
        row["mfu_peak_dtype"] = peak_dtype
    if bytes_accessed is not None:
        # the roofline as a measurement: XLA's post-fusion bytes-accessed
        # estimate over the measured step time. hbm_peak_frac ≳ 0.75 says
        # the step is at the bandwidth wall (the estimate over-counts true
        # traffic somewhat, so 1.0 is not reachable); well below that, the
        # gap is schedule/compute, not bandwidth (docs/performance.md
        # "Roofline, measured").
        row["bytes_per_step_gb"] = round(bytes_accessed / 1e9, 2)
        row["achieved_gbps"] = round(bytes_accessed / step_s / 1e9, 1)
        if peak_bw is not None:
            row["hbm_peak_frac"] = round(bytes_accessed / step_s / peak_bw, 4)
    if breakdown is not None and breakdown["agg"]:
        row["step_breakdown_ms"] = breakdown["agg"]
        row["breakdown_source"] = breakdown["source"]
    return row


def _e2e_metric_name(arch: str, on_accel: bool, platform: str) -> str:
    """JSON metric name for the end-to-end row — locked by
    tests/test_bench_meta.py so the schema cannot drift silently."""
    return (f"{arch}_e2e_images_per_sec_per_chip"
            + ("" if on_accel else f"_{platform}"))


def _bench_e2e_row(cfg, mesh, *, steps: int, warmup: int, metric: str,
                   n_chips: int, dataset_kind: str, root: str, n_images: int,
                   src_size: int, device_prefetch: int, num_workers: int,
                   h2d_overlap: bool = False):
    """End-to-end throughput: the real `ShardedLoader → DevicePrefetcher →
    jitted train step` path against an actual dataset — the one stage
    neither the device-only rows (input excluded by design) nor
    bench_input.py (host-only) measures: host batch assembly + H2D staging
    overlapping device compute. The number is gated by whichever of {host
    input rate, H2D staging, device step} binds, so read it NEXT TO the
    device-only row: e2e ≈ device-only means the input path keeps up;
    e2e well below it localizes the stall to the host/H2D side.
    """
    import jax
    from ddp_classification_pytorch_tpu.data import ShardedLoader
    from ddp_classification_pytorch_tpu.data.device_prefetch import DevicePrefetcher
    from ddp_classification_pytorch_tpu.train.loop import make_native_batcher
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    import numpy as np
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    batcher = None
    if dataset_kind == "imagefolder":
        from bench_input import ensure_dataset
        from ddp_classification_pytorch_tpu.data import (ImageFolderDataset,
                                                         build_transform)

        ensure_dataset(root, n_images, src_size)
        tf = build_transform("baseline", train=True,
                             image_size=cfg.data.image_size,
                             out_dtype=cfg.data.input_dtype)
        ds = ImageFolderDataset.from_root(root, tf)
        batcher = make_native_batcher(ds, cfg, train=True)
        input_path = "native" if batcher is not None else "python"
    else:
        from ddp_classification_pytorch_tpu.data import SyntheticDataset

        ds = SyntheticDataset(n_images, cfg.data.image_size,
                              cfg.data.num_classes,
                              out_dtype=cfg.data.input_dtype)
        input_path = "synthetic"

    batch = cfg.data.batch_size
    loader = ShardedLoader(ds, batch, shuffle=True, seed=cfg.run.seed,
                           num_workers=num_workers,
                           prefetch=cfg.data.prefetch, batcher=batcher)
    # wire-format evidence, captured from the REAL first host batch (not
    # recomputed from config): per-step H2D payload bytes and the dtype
    # that actually crossed — the uint8 dataplane's ~4× cut shows up here
    wire: dict = {}
    sharding = meshlib.batch_sharding(mesh)

    def assemble(batch_idx, host_batch):
        if not wire:
            images, labels = host_batch
            wire["h2d_bytes_per_step"] = int(
                np.asarray(images).nbytes + np.asarray(labels).nbytes)
            wire["input_dtype"] = str(np.asarray(images).dtype)
        return meshlib.make_global_array(host_batch, mesh, sharding=sharding)

    prefetcher = DevicePrefetcher(loader, mesh, depth=device_prefetch,
                                  assemble=assemble, overlap=h2d_overlap)
    main_ident = __import__("threading").get_ident()
    # consumer-side input-wait evidence: time the step loop spends BLOCKED
    # on the prefetcher (host fetch + H2D staging not keeping up) — the
    # h2d-attributed idle the overlap mode exists to shrink
    wait = {"s": 0.0, "n": 0}

    def batches():
        epoch = 0
        while True:  # as many epochs as warmup+steps need
            loader.set_epoch(epoch)
            for b in prefetcher:
                yield b
            epoch += 1

    it = None
    donation: dict = {}
    try:
        with mesh:
            model, tx, state = create_train_state(
                cfg, mesh, steps_per_epoch=max(len(loader), 1))
            step = make_train_step(cfg, model, tx, mesh=mesh)
            # donation + comms/memory evidence (the ROADMAP's MFU item owes
            # a donation audit so no step buffer round-trips HBM): ONE AOT
            # compile during the warmup window — the persistent cache makes
            # it a cache hit on TPU — reads the executable's alias table,
            # collective inventory, and memory budget in a single pass
            try:
                from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
                    step_comms_evidence)
                from ddp_classification_pytorch_tpu.parallel.mesh import (
                    batch_sharding)

                h = cfg.data.image_size
                np_dt = np.uint8 if cfg.data.input_dtype == "uint8" else np.float32
                # the batch avals carry the data-axis sharding the real run
                # uses (make_global_array's layout) — an unannotated aval
                # would compile a fully-replicated program whose collective
                # inventory is empty, not the hot step's
                sh = batch_sharding(mesh)
                donation = step_comms_evidence(step, (
                    state,
                    jax.ShapeDtypeStruct((batch, h, h, 3), np_dt, sharding=sh),
                    jax.ShapeDtypeStruct((batch,), np.int32, sharding=sh)),
                    mesh=mesh)
                # numerics evidence off the SAME avals (one extra trace, no
                # compile): bf16-op fraction + the unwaivable dtype
                # contracts (dtype audit D1/D3/D4/D6)
                from ddp_classification_pytorch_tpu.analysis.dtype_audit import (
                    step_dtype_evidence)

                donation.update(step_dtype_evidence(step, (
                    state,
                    jax.ShapeDtypeStruct((batch, h, h, 3), np_dt, sharding=sh),
                    jax.ShapeDtypeStruct((batch,), np.int32, sharding=sh))))
            except Exception as e:  # evidence must never cost the row
                print(f"# donation evidence failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            it = batches()
            metrics = None
            for _ in range(max(warmup, 1)):  # >=1: compile outside the window
                state, metrics = step(state, *next(it))
            float(metrics["loss"])  # hard sync (device-get, see _bench_row)
            t0 = time.perf_counter()
            for _ in range(steps):
                w0 = time.perf_counter()
                b = next(it)
                wait["s"] += time.perf_counter() - w0
                wait["n"] += 1
                state, metrics = step(state, *b)
            float(metrics["loss"])  # hard sync closes the timing window
            step_s = (time.perf_counter() - t0) / steps
    finally:
        if it is not None:
            it.close()  # unwinds the prefetcher + its stager thread
        loader.close()

    return {
        "metric": metric,
        "value": round(batch / step_s / n_chips, 2),
        "unit": "images/sec/chip",
        "step_ms": round(step_s * 1e3, 2),
        "device_prefetch": device_prefetch,
        "input": input_path,
        "host_workers": num_workers,
        # K-microbatch accumulation: the jitted step scans grad_accum
        # microbatches into an f32 accumulator and defers the cross-replica
        # gradient reduction to ONE collective per optimizer step, so
        # collective_bytes_per_optimizer_step stays ~flat while per-
        # microbatch reduction bytes fall ÷K (÷2K with the bf16 wire)
        "grad_accum": max(int(cfg.parallel.grad_accum), 1),
        "collective_bytes_per_optimizer_step": donation.get(
            "collective_bytes_per_step", 0),
        # double-buffered H2D dispatch + what the step loop actually waited
        # on the input path (host fetch/H2D staging behind the step)
        "h2d_overlap": bool(h2d_overlap) and device_prefetch > 0,
        "h2d_wait_ms_per_step": round(
            wait["s"] / max(wait["n"], 1) * 1e3, 3),
        # wire-format evidence (uint8 dataplane): observed per-step H2D
        # payload bytes + the dtype that actually crossed the wire
        "h2d_bytes_per_step": wire.get("h2d_bytes_per_step", 0),
        "input_dtype": wire.get("input_dtype", cfg.data.input_dtype),
        # evidence the overlap actually ran: how many batches the stager
        # assembled, and whether assembly happened off the consumer thread
        "staged_batches": prefetcher.staged,
        "staged_off_thread": (prefetcher.stager_thread is not None
                              and prefetcher.stager_thread != main_ident),
        # donation audit evidence (analysis/jaxpr_audit.donation_evidence):
        # every donated state byte must be aliased in the executable, else
        # that buffer round-trips HBM every step (coverage < 1.0 = finding)
        "donated_bytes": donation.get("donated_bytes", 0),
        "aliased_bytes": donation.get("aliased_bytes", 0),
        "donation_coverage": donation.get("donation_coverage"),
        "temp_bytes": donation.get("temp_bytes"),
        # comms/memory evidence from the SAME compile (sharding_audit):
        # per-step collective payload and the executable's peak HBM — the
        # numbers `cli.analyze --diff-baseline` fences between TPU windows
        "collective_bytes_per_step": donation.get(
            "collective_bytes_per_step", 0),
        "peak_hbm_bytes": donation.get("peak_hbm_bytes", 0),
        # numerics evidence (analysis/dtype_audit.step_dtype_evidence):
        # FLOP-weighted fraction of matmul/conv work at bf16 (the MFU
        # roofline's peak-dtype witness) and whether the unwaivable dtype
        # contracts hold in the compiled-from-this-trace program
        "bf16_op_fraction": donation.get("bf16_op_fraction"),
        "accum_dtype_ok": donation.get("accum_dtype_ok"),
    }


def _serve_metric_name(arch: str, on_accel: bool, platform: str) -> str:
    """JSON metric name for the serving-latency row — locked by
    tests/test_bench_meta.py so the schema cannot drift silently."""
    return (f"{arch}_serve_latency"
            + ("" if on_accel else f"_{platform}"))


def _serve_slo_metric_name(arch: str, on_accel: bool, platform: str) -> str:
    """JSON metric name for the SLO-search row (max sustainable offered
    rps at a p99 latency SLO) — locked by tests/test_bench_meta.py."""
    return (f"{arch}_max_rps_at_p99_slo"
            + ("" if on_accel else f"_{platform}"))


def _bench_serve_slo_row(cfg, mesh, *, metric: str, slo_p99_ms: float,
                         max_rps: float, iters: int, n_requests: int,
                         buckets, max_batch: int, timeout_ms: float,
                         topk: int, seed: int = 0):
    """Closed-loop offered-load search: the max sustainable requests/s at
    a p99 latency SLO, on ONE warm `ServingEngine` (every bucket compiled
    before the first probe, so no probe pays a compile).

    Each probe paces `n_requests` submissions on the ideal schedule for a
    candidate offered rps and measures the end-to-end p99 (submit → top-k
    answer) from the returned predictions themselves — a fresh sample per
    probe, not the engine's cumulative window. The search is a bisection
    over [0, max_rps]: a probe holding the SLO raises the floor, a breach
    lowers the ceiling; the reported value is the highest KNOWN-GOOD rps
    (the floor), never an extrapolation. The probe ladder rides along in
    the row so a regression is diagnosable from the JSON alone
    (docs/serving.md "SLO search")."""
    import tempfile

    import numpy as np

    from ddp_classification_pytorch_tpu.config import dp_round_up_buckets
    from ddp_classification_pytorch_tpu.parallel.mesh import DATA_AXIS
    from ddp_classification_pytorch_tpu.serve.engine import ServingEngine
    from ddp_classification_pytorch_tpu.serve.metrics import (
        ServeMetrics,
        percentile,
    )
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_topk_predict_step

    with mesh, tempfile.TemporaryDirectory() as tmp:
        dp = int(dict(mesh.shape).get(DATA_AXIS, 1))
        buckets = dp_round_up_buckets(buckets, dp)
        model, _, state = create_train_state(cfg, mesh, steps_per_epoch=100)
        predict = make_topk_predict_step(cfg, model, topk, mesh=mesh)
        engine = ServingEngine(
            state, predict,
            image_size=cfg.data.image_size,
            input_dtype=cfg.data.input_dtype,
            max_batch=max_batch, batch_timeout_ms=timeout_ms,
            queue_depth=max(n_requests, 64), buckets=buckets,
            metrics=ServeMetrics(latency_window=max(n_requests, 2048)),
            mesh=mesh, aot_dir=os.path.join(tmp, "aot"))
        engine.warmup()
        engine.start()
        rng = np.random.default_rng(seed)
        h = cfg.data.image_size
        n_distinct = min(n_requests, 16)
        pool = (rng.integers(0, 256, (n_distinct, h, h, 3)).astype(np.uint8)
                if cfg.data.input_dtype == "uint8"
                else rng.normal(size=(n_distinct, h, h, 3)).astype(np.float32))

        def probe_p99(rps: float) -> float:
            t0 = time.perf_counter()
            futures = []
            for i in range(n_requests):
                lag = t0 + i / rps - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                futures.append(engine.submit(pool[i % n_distinct]))
            lats = sorted(f.result(timeout=120).latency_ms for f in futures)
            return percentile(lats, 99)

        probes = []
        lo, lo_p99 = 0.0, 0.0
        hi = float(max_rps)
        # ceiling probe first: if even max_rps holds the SLO there is
        # nothing to bisect — the bound, not the engine, is the limit
        p99 = probe_p99(hi)
        probes.append({"rps": round(hi, 2), "p99_ms": round(p99, 3),
                       "ok": p99 <= slo_p99_ms})
        if p99 <= slo_p99_ms:
            lo, lo_p99 = hi, p99
        else:
            for _ in range(max(int(iters), 1)):
                mid = (lo + hi) / 2.0
                p99 = probe_p99(mid)
                ok = p99 <= slo_p99_ms
                probes.append({"rps": round(mid, 2),
                               "p99_ms": round(p99, 3), "ok": ok})
                if ok:
                    lo, lo_p99 = mid, p99
                else:
                    hi = mid
        engine.drain()

    return {
        "metric": metric,
        "unit": "rps",
        "value": round(lo, 2),
        "p99_slo_ms": slo_p99_ms,
        "p99_at_max_ms": round(lo_p99, 3),
        "slo_bound_rps": float(max_rps),
        "bound_limited": bool(probes[0]["ok"]),
        "iterations": len(probes),
        "n_requests_per_probe": n_requests,
        "probes": probes,
        "topk": topk,
        "max_batch": max_batch,
        "batch_timeout_ms": timeout_ms,
        "buckets": list(buckets),
        "serve_devices": int(engine.serve_devices),
    }


def _bench_serve_row(cfg, mesh, *, metric: str, n_requests: int,
                     offered_rps: float, buckets, max_batch: int,
                     timeout_ms: float, topk: int, seed: int = 0):
    """Serving-path latency/throughput: the real `ServingEngine` (bounded
    queue → deadline batcher → bucket-padded jitted predict) under a fixed
    offered load. Buckets are compiled in warmup, so the measured window
    contains zero compiles — the row reports end-to-end request latency
    percentiles (submit → top-k result), achieved requests/s, and the
    bucket histogram + fill ratio as evidence of how the batcher actually
    packed the traffic (docs/serving.md)."""
    import tempfile

    import numpy as np

    from ddp_classification_pytorch_tpu.config import dp_round_up_buckets
    from ddp_classification_pytorch_tpu.parallel.mesh import DATA_AXIS
    from ddp_classification_pytorch_tpu.serve.engine import ServingEngine
    from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_topk_predict_step

    with mesh, tempfile.TemporaryDirectory() as tmp:
        # dp-sharded serving: padded buckets shard over the mesh's data
        # axis, so round the requested buckets up to dp multiples (the
        # same helper ServeConfig auto-buckets ride)
        dp = int(dict(mesh.shape).get(DATA_AXIS, 1))
        buckets = dp_round_up_buckets(buckets, dp)
        aot_dir = os.path.join(tmp, "aot")
        model, _, state = create_train_state(cfg, mesh, steps_per_epoch=100)
        metrics = ServeMetrics(latency_window=max(n_requests, 2048))

        def build_engine(m):
            # a FRESH predict per engine: the cold/warm split must measure
            # the AOT sidecar, not a warm jit cache shared between boots
            predict = make_topk_predict_step(cfg, model, topk, mesh=mesh)
            return ServingEngine(
                state, predict,
                image_size=cfg.data.image_size,
                input_dtype=cfg.data.input_dtype,
                max_batch=max_batch, batch_timeout_ms=timeout_ms,
                queue_depth=max(n_requests, 64), buckets=buckets, metrics=m,
                mesh=mesh, aot_dir=aot_dir)

        # cold start: empty sidecar → warmup compiles every bucket and
        # banks the executables; warm start: a second replica deserializes
        # them — the cold/warm delta IS the instant-cold-start evidence
        cold_engine = build_engine(ServeMetrics())
        t_cold = time.perf_counter()
        cold_engine.warmup()
        cold_start_ms = (time.perf_counter() - t_cold) * 1e3
        cold_engine.drain()
        engine = build_engine(metrics)
        t_warm = time.perf_counter()
        engine.warmup()  # all bucket programs readied outside the window
        warm_start_ms = (time.perf_counter() - t_warm) * 1e3
        engine.start()
        rng = np.random.default_rng(seed)
        h = cfg.data.image_size
        n_distinct = min(n_requests, 16)
        pool = (rng.integers(0, 256, (n_distinct, h, h, 3)).astype(np.uint8)
                if cfg.data.input_dtype == "uint8"
                else rng.normal(size=(n_distinct, h, h, 3)).astype(np.float32))
        t0 = time.perf_counter()
        futures = []
        for i in range(n_requests):
            if offered_rps:
                # fixed offered load: pace submissions on the ideal schedule
                lag = t0 + i / offered_rps - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
            futures.append(engine.submit(pool[i % n_distinct]))
        for f in futures:
            f.result(timeout=120)
        elapsed = time.perf_counter() - t0
        engine.drain()

    snap = metrics.snapshot()
    return {
        "metric": metric,
        "unit": "ms",
        "p50_ms": snap["p50_ms"],
        "p95_ms": snap["p95_ms"],
        "p99_ms": snap["p99_ms"],
        "requests_per_sec": round(n_requests / elapsed, 2),
        "offered_rps": offered_rps or 0.0,
        "n_requests": n_requests,
        "topk": topk,
        "max_batch": max_batch,
        "batch_timeout_ms": timeout_ms,
        "buckets": list(buckets),
        # batching evidence: how the deadline batcher actually packed the
        # offered load, and that only bucket shapes ever ran
        "bucket_hist": {str(k): v for k, v in sorted(snap["bucket_hist"].items())},
        "fill_ratio": snap["fill_ratio"],
        "compiled_buckets": sorted(engine.seen_buckets),
        # replica boot evidence (serve/aot.py): first boot compiles + banks
        # the bucket executables, second deserializes them — warm must beat
        # cold, and the hit flag proves the sidecar (not a jit cache) did it
        "cold_start_ms": round(cold_start_ms, 1),
        "warm_start_ms": round(warm_start_ms, 1),
        "aot_cache_hit": bool(engine.aot_hit),
        "serve_devices": int(engine.serve_devices),
    }


def main() -> None:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--batch", type=int, default=0, help="global batch; 0 = auto")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--trace", action="store_true",
                    help="profile the flagship's timed window "
                         "(jax.profiler trace) and "
                         "emit step_breakdown_ms — per-step fwd/bwd/"
                         "optimizer/collectives/h2d/idle ms — next to the "
                         "roofline fields")
    ap.add_argument("--deadline", type=float, default=900.0,
                    help="total wall-clock budget in seconds; 0 = unbounded. "
                         "Extra rows are skipped when the remaining budget "
                         "is too thin for another compile.")
    ap.add_argument("--rows", default="arcface,vit",
                    help="comma list of extra rows (arcface, vit); '' = none")
    ap.add_argument("--e2e", action="store_true",
                    help="also measure the end-to-end input path: the real "
                         "ShardedLoader → DevicePrefetcher → train-step "
                         "pipeline against an on-disk image folder "
                         "(synthetic data on CPU), emitted as an "
                         "<arch>_e2e_images_per_sec_per_chip extra row")
    ap.add_argument("--e2e-dataset", default="",
                    choices=["", "imagefolder", "synthetic"],
                    help="'' = imagefolder on accelerators, synthetic on CPU")
    ap.add_argument("--e2e-root", default="/tmp/bench_imgds",
                    help="generated image-folder root for --e2e (shared "
                         "with bench_input.py)")
    ap.add_argument("--e2e-images", type=int, default=1024)
    ap.add_argument("--e2e-src-size", type=int, default=320,
                    help="source JPEG side for the generated folder")
    ap.add_argument("--e2e-workers", type=int, default=0,
                    help="host loader threads for --e2e; 0 = cpu count")
    ap.add_argument("--device-prefetch", type=int, default=2,
                    help="DevicePrefetcher depth for --e2e (0 = synchronous)")
    ap.add_argument("--input-dtype", default="uint8",
                    choices=["uint8", "float32"],
                    help="H2D wire format for --e2e (data.input_dtype): "
                         "uint8 ships raw pixels at ¼ the bytes with "
                         "on-device normalization; float32 is the legacy "
                         "host-normalize wire. The row's h2d_bytes_per_step "
                         "/ input_dtype fields record what actually crossed")
    ap.add_argument("--zero-opt", default="auto",
                    choices=["auto", "on", "off"],
                    help="parallel.zero_opt for the train rows: ZeRO-1 "
                         "optimizer-state sharding over the data axis. The "
                         "e2e row's collective_bytes_per_step/peak_hbm_bytes "
                         "evidence records the payload/footprint difference "
                         "('off' to A/B against the replicated-state step)")
    ap.add_argument("--grad-reduce-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="parallel.grad_reduce_dtype for the train rows: "
                         "bfloat16 halves the gradient-reduction wire "
                         "payload (master params/momentum stay f32); shows "
                         "up in the e2e row's collective_bytes_per_step")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="parallel.grad_accum for the train rows: scan K "
                         "microbatches per optimizer step inside the jitted "
                         "program with ONE deferred gradient reduction, so "
                         "the e2e row's collective_bytes_per_optimizer_step "
                         "stays ~flat while per-microbatch reduction bytes "
                         "fall ÷K (compose with --grad-reduce-dtype "
                         "bfloat16 for ÷2K); K must divide the per-replica "
                         "batch")
    ap.add_argument("--h2d-overlap", action="store_true",
                    help="double-buffered H2D dispatch for --e2e: fetch "
                         "host batch N+1 on a separate thread while batch "
                         "N's make_global_array transfer is in flight "
                         "(one-slot in-flight budget; the row carries "
                         "h2d_overlap + h2d_wait_ms_per_step as evidence)")
    ap.add_argument("--serve", action="store_true",
                    help="also measure the serving path: the ServingEngine "
                         "(bounded queue → deadline batcher → bucketed "
                         "jitted predict, serve/engine.py) under a fixed "
                         "offered load, emitted as an <arch>_serve_latency "
                         "extra row (p50/p99 latency, req/s, bucket "
                         "histogram)")
    ap.add_argument("--serve-requests", type=int, default=256,
                    help="requests to push through the engine for --serve")
    ap.add_argument("--serve-rps", type=float, default=0.0,
                    help="offered load in requests/s for --serve "
                         "(0 = submit as fast as possible)")
    ap.add_argument("--serve-buckets", default="1,4,16",
                    help="comma list of padded batch shapes for --serve")
    ap.add_argument("--serve-max-batch", type=int, default=16,
                    help="deadline batcher's largest micro-batch for --serve")
    ap.add_argument("--serve-timeout-ms", type=float, default=5.0,
                    help="partial-batch flush deadline for --serve")
    ap.add_argument("--serve-slo-p99-ms", type=float, default=0.0,
                    help="with --serve: also run the closed-loop offered-"
                         "load search for the max sustainable rps whose "
                         "measured p99 stays under this SLO, emitted as an "
                         "<arch>_max_rps_at_p99_slo extra row (0 = off)")
    ap.add_argument("--serve-slo-max-rps", type=float, default=512.0,
                    help="upper bound of the SLO search's bisection over "
                         "offered rps (the ceiling probe runs first; if it "
                         "holds the SLO the row reports bound_limited)")
    ap.add_argument("--serve-slo-iters", type=int, default=6,
                    help="bisection iterations for the SLO search (each "
                         "probe pushes --serve-requests paced submissions)")
    args = ap.parse_args()

    def remaining() -> float:
        if not args.deadline:
            return float("inf")
        return args.deadline - (time.monotonic() - t_start)

    from ddp_classification_pytorch_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()  # the driver re-benches every round

    import jax

    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    devices = jax.devices()
    n_chips = len(devices)
    platform = devices[0].platform
    on_accel = platform in ("tpu", "gpu")
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        # a measurement path that finds no chip fails; only an EXPLICIT
        # JAX_PLATFORMS=cpu (the tests' rehearsal) may run the tiny CPU rows
        print(f"# no TPU found (platform {platform!r}); set JAX_PLATFORMS=cpu "
              "for the CPU rehearsal", file=sys.stderr)
        sys.exit(3)
    peak = _peak_flops(devices[0].device_kind) if platform == "tpu" else None
    peak_bw = _peak_hbm(devices[0].device_kind) if platform == "tpu" else None

    mesh = meshlib.make_mesh(devices=devices)

    cfg = get_preset("baseline")
    cfg.model.arch = args.arch
    cfg.model.dtype = "bfloat16" if on_accel else "float32"
    # ZeRO-1 / wire-dtype knobs reach every train row through cfg.parallel;
    # the e2e row's step_comms_evidence (collective_bytes_per_step,
    # peak_hbm_bytes) is where their effect is machine-visible
    cfg.parallel.zero_opt = args.zero_opt
    cfg.parallel.grad_reduce_dtype = args.grad_reduce_dtype
    cfg.parallel.grad_accum = max(args.grad_accum, 1)
    cfg.data.num_classes = 1000
    # CPU caps (not pins) the image size so smoke runs can shrink further
    cfg.data.image_size = args.image_size if on_accel else min(args.image_size, 64)
    # 128/chip is the RN50/224 default the docs settle on
    # (docs/performance.md; no figure for it is measured on this machine)
    cfg.data.batch_size = args.batch or (128 * n_chips if on_accel else 8 * n_chips)
    steps = max(args.steps, 1) if on_accel else 3
    warmup = max(args.warmup, 0) if on_accel else 1

    main_row = _bench_row(
        cfg, mesh, steps=steps, warmup=warmup, n_chips=n_chips, peak=peak,
        peak_bw=peak_bw, trace=args.trace,
        metric=f"{args.arch}_train_images_per_sec_per_chip"
        + ("" if on_accel else f"_{platform}"),
    )
    main_row["vs_baseline"] = round(main_row["value"] / A100_RESNET50_IMG_PER_SEC, 4)
    print(
        f"# flagship: {platform} x{n_chips}, batch {cfg.data.batch_size}, "
        f"{cfg.data.image_size}px, {steps} steps, step {main_row['step_ms']}ms, "
        f"mfu {main_row.get('mfu', 'n/a')}, {remaining():.0f}s budget left",
        file=sys.stderr,
    )
    if "step_breakdown_ms" in main_row:
        b = main_row["step_breakdown_ms"]
        print("# breakdown ({}): ".format(main_row["breakdown_source"])
              + " ".join(f"{k}={b[k]}ms" for k in
                         ("fwd", "bwd", "optimizer", "collectives",
                          "h2d", "idle")),
              file=sys.stderr)

    # Extra rows: one representative per additional parallelism surface the
    # driver should see regress. Each needs its own compile,
    # so only start a row while a conservative slice of budget remains.
    extra = []
    failed_rows = 0  # a failed row keeps the flagship line but not rc 0
    row_budget = 240.0  # compile + measure headroom per row
    for name in [r for r in args.rows.split(",") if r]:
        if remaining() < row_budget:
            print(f"# skipping extra row {name!r}: {remaining():.0f}s left "
                  f"< {row_budget:.0f}s budget", file=sys.stderr)
            continue
        try:
            if name == "arcface":
                c = get_preset("arcface")
                c.model.dtype = cfg.model.dtype
                c.data.image_size = cfg.data.image_size
                c.data.batch_size = (128 if on_accel else 8) * n_chips
                # partial-FC path needs a model axis > 1; on a single chip
                # the dense margin head is the honest measurement
                label = "arcface_resnet50"
                if n_chips >= 2:
                    c.parallel.model_axis = 2
                    c.parallel.arcface_sharded_ce = True
                    # class-sharded head needs C % mp == 0; round the
                    # reference's 2173 up — perf-neutral, noted in the metric
                    mp = c.parallel.model_axis
                    c.data.num_classes = -(-c.data.num_classes // mp) * mp
                    label += "_sharded_ce"
                row_mesh = meshlib.make_mesh(
                    meshlib.MeshSpec(model_parallel=c.parallel.model_axis),
                    devices=devices)
            elif name == "vit":
                c = get_preset("baseline")
                c.model.arch = "vit_s16"
                # auto-pick: flash kernel at/above flash_min_tokens, XLA
                # fused dense below (196 tokens at 224px → dense, the
                # equal-or-better path there; docs/performance.md knob #4)
                c.model.flash_attention = True
                c.model.dtype = cfg.model.dtype
                c.data.num_classes = 1000
                c.data.image_size = cfg.data.image_size
                c.data.batch_size = (128 if on_accel else 8) * n_chips
                tokens = (c.data.image_size // 16) ** 2
                label = ("vit_s16_flash" if tokens >= c.model.flash_min_tokens
                         else "vit_s16_dense_auto")
                row_mesh = mesh
            else:
                print(f"# unknown extra row {name!r}", file=sys.stderr)
                continue
            row = _bench_row(
                c, row_mesh, steps=max(steps // 2, 1), warmup=max(warmup // 2, 1),
                n_chips=n_chips, peak=peak, peak_bw=peak_bw,
                metric=f"{label}_train_images_per_sec_per_chip"
                + ("" if on_accel else f"_{platform}"),
            )
            extra.append(row)
            print(f"# extra row {name}: {row['value']} img/s/chip, "
                  f"step {row['step_ms']}ms, mfu {row.get('mfu', 'n/a')}",
                  file=sys.stderr)
        except Exception as e:  # a broken extra row must not cost the flagship line
            print(f"# extra row {name!r} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed_rows += 1

    if args.e2e:
        e2e_budget = 180.0  # one jit compile + a dataset pass
        if remaining() < e2e_budget:
            print(f"# skipping e2e row: {remaining():.0f}s left "
                  f"< {e2e_budget:.0f}s budget", file=sys.stderr)
        else:
            try:
                kind = args.e2e_dataset or (
                    "imagefolder" if on_accel else "synthetic")
                cfg.data.input_dtype = args.input_dtype
                row = _bench_e2e_row(
                    cfg, mesh, steps=steps, warmup=max(warmup // 2, 1),
                    metric=_e2e_metric_name(args.arch, on_accel, platform),
                    n_chips=n_chips, dataset_kind=kind, root=args.e2e_root,
                    n_images=args.e2e_images, src_size=args.e2e_src_size,
                    device_prefetch=args.device_prefetch,
                    num_workers=args.e2e_workers or (os.cpu_count() or 4),
                    h2d_overlap=args.h2d_overlap,
                )
                extra.append(row)
                print(f"# e2e row ({row['input']}, prefetch "
                      f"{row['device_prefetch']}, overlap "
                      f"{row['h2d_overlap']}, accum {row['grad_accum']}, "
                      f"wire {row['input_dtype']} "
                      f"{row['h2d_bytes_per_step']} B/step): "
                      f"{row['value']} img/s/chip, "
                      f"step {row['step_ms']}ms, staged "
                      f"{row['staged_batches']} off-thread="
                      f"{row['staged_off_thread']}", file=sys.stderr)
            except Exception as e:  # e2e must not cost the flagship line either
                print(f"# e2e row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
                failed_rows += 1

    if args.serve:
        serve_budget = 180.0  # len(buckets) predict compiles + the load run
        if remaining() < serve_budget:
            print(f"# skipping serve row: {remaining():.0f}s left "
                  f"< {serve_budget:.0f}s budget", file=sys.stderr)
        else:
            try:
                scfg = get_preset("baseline")
                scfg.model.arch = args.arch
                scfg.model.dtype = cfg.model.dtype
                scfg.data.num_classes = 1000
                scfg.data.image_size = cfg.data.image_size
                buckets = tuple(int(b) for b in args.serve_buckets.split(",") if b)
                n_req = args.serve_requests if on_accel else min(
                    args.serve_requests, 24)
                row = _bench_serve_row(
                    scfg, mesh,
                    metric=_serve_metric_name(args.arch, on_accel, platform),
                    n_requests=n_req, offered_rps=args.serve_rps,
                    buckets=buckets, max_batch=args.serve_max_batch,
                    timeout_ms=args.serve_timeout_ms, topk=5)
                extra.append(row)
                print(f"# serve row: p50 {row['p50_ms']}ms p99 "
                      f"{row['p99_ms']}ms, {row['requests_per_sec']} req/s, "
                      f"fill {row['fill_ratio']}, buckets "
                      f"{row['bucket_hist']}", file=sys.stderr)
            except Exception as e:  # serve must not cost the flagship line
                print(f"# serve row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
                failed_rows += 1

    if args.serve and args.serve_slo_p99_ms > 0:
        # the search is a ladder of paced load runs on one warm engine:
        # budget it like the serve row plus one run per bisection step
        slo_budget = 180.0 + 10.0 * max(args.serve_slo_iters, 1)
        if remaining() < slo_budget:
            print(f"# skipping SLO search row: {remaining():.0f}s left "
                  f"< {slo_budget:.0f}s budget", file=sys.stderr)
        elif args.serve_slo_max_rps <= 0:
            print("# skipping SLO search row: --serve-slo-max-rps must be "
                  "> 0", file=sys.stderr)
        else:
            try:
                scfg = get_preset("baseline")
                scfg.model.arch = args.arch
                scfg.model.dtype = cfg.model.dtype
                scfg.data.num_classes = 1000
                scfg.data.image_size = cfg.data.image_size
                buckets = tuple(int(b) for b in args.serve_buckets.split(",") if b)
                n_req = args.serve_requests if on_accel else min(
                    args.serve_requests, 24)
                row = _bench_serve_slo_row(
                    scfg, mesh,
                    metric=_serve_slo_metric_name(args.arch, on_accel,
                                                  platform),
                    slo_p99_ms=args.serve_slo_p99_ms,
                    max_rps=args.serve_slo_max_rps,
                    iters=args.serve_slo_iters,
                    n_requests=n_req, buckets=buckets,
                    max_batch=args.serve_max_batch,
                    timeout_ms=args.serve_timeout_ms, topk=5)
                extra.append(row)
                print(f"# SLO search row: {row['value']} rps sustains "
                      f"p99 <= {row['p99_slo_ms']}ms "
                      f"(measured {row['p99_at_max_ms']}ms, "
                      f"{row['iterations']} probes, bound_limited="
                      f"{row['bound_limited']})", file=sys.stderr)
            except Exception as e:  # the search must not cost the flagship line
                print(f"# SLO search row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
                failed_rows += 1

    if extra:
        main_row["extra"] = extra
    print(json.dumps(main_row), flush=True)
    print(
        f"# {platform} x{n_chips} ({devices[0].device_kind}), dtype "
        f"{cfg.model.dtype}, {time.monotonic() - t_start:.0f}s total",
        file=sys.stderr,
    )
    if failed_rows:
        sys.exit(1)


if __name__ == "__main__":
    main()
