"""bench.py schema and start-up rules: metric names, flags, the CPU
rehearsal, and that no chip means no row."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unknown_device_kind_is_an_error():
    """Peaks are keyed by device_kind with their source; a device that is
    not in the table is an error, never a silent None/default."""
    import bench

    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_hbm("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="TPU v99"):
        bench._peak_flops("TPU v99")


def test_no_tpu_without_explicit_cpu_exits_nonzero():
    """A measurement path that finds no chip fails: only an explicit
    JAX_PLATFORMS=cpu (the tests' rehearsal) may print the tiny CPU rows.
    The platform is pinned through jax.config here, so the environment
    variable is genuinely absent while no TPU library is touched."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    src = ("import sys, jax\n"
           "jax.config.update('jax_platforms', 'cpu')\n"
           "import bench\n"
           "sys.argv = ['bench.py', '--rows', '']\n"
           "bench.main()\n")
    p = subprocess.run([sys.executable, "-c", src], cwd=REPO, env=env,
                       capture_output=True, timeout=120)
    assert p.returncode == 3, (p.returncode, p.stderr[-300:])
    assert p.stdout.strip() == b"", p.stdout[-300:]
    assert b"no TPU found" in p.stderr


def test_bench_cpu_rehearsal_prints_its_json_line():
    """`JAX_PLATFORMS=cpu python bench.py` still ends in ONE JSON line with
    `_cpu`-suffixed metric names, and carries none of the removed
    outage-replay keys."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench.py", "--arch", "resnet18", "--rows", "",
         "--image-size", "32", "--batch", "8"], cwd=REPO, env=env,
        capture_output=True, timeout=600)
    assert p.returncode == 0, p.stderr[-500:]
    row = json.loads(p.stdout.decode().strip().splitlines()[-1])
    assert row["metric"] == "resnet18_train_images_per_sec_per_chip_cpu"
    assert row["value"] > 0 and row["accum_dtype_ok"] is True
    for gone in ("last_known_good", "probe", "contention", "backend"):
        assert gone not in row, gone


def test_e2e_metric_name_schema():
    """Lock the e2e row's metric naming: the TPU capture must emit exactly
    `resnet50_e2e_images_per_sec_per_chip` (regression-guarded next to the
    device-only flagship row), with the standard platform suffix off-accel."""
    import bench

    assert (bench._e2e_metric_name("resnet50", True, "tpu")
            == "resnet50_e2e_images_per_sec_per_chip")
    assert (bench._e2e_metric_name("resnet18", False, "cpu")
            == "resnet18_e2e_images_per_sec_per_chip_cpu")


def test_bench_cli_has_e2e_flags():
    """The --e2e surface must keep parsing (the smoke below drives the row
    builder directly, so argparse drift would otherwise go unseen)."""
    p = subprocess.run([sys.executable, "bench.py", "--help"], cwd=REPO,
                       capture_output=True, timeout=60)
    assert p.returncode == 0, p.stderr[-300:]
    helptext = p.stdout.decode()
    for flag in ("--e2e", "--e2e-dataset", "--e2e-images", "--e2e-root",
                 "--device-prefetch", "--e2e-workers", "--input-dtype",
                 "--trace", "--grad-accum", "--h2d-overlap"):
        assert flag in helptext, flag


def test_bench_e2e_row_smoke_cpu():
    """Run the e2e bench path (the exact `_bench_e2e_row` that `bench.py
    --e2e` calls) for a handful of steps on the CPU backend with a tiny
    synthetic dataset, and lock the emitted row's schema: the driver's
    regression guard keys on these fields."""
    import jax

    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    cfg.data.batch_size = 16
    mesh = meshlib.make_mesh()
    n_chips = len(jax.devices())
    metric = bench._e2e_metric_name("resnet18", False, "cpu")
    row = bench._bench_e2e_row(
        cfg, mesh, steps=2, warmup=1, metric=metric, n_chips=n_chips,
        dataset_kind="synthetic", root="", n_images=64, src_size=0,
        device_prefetch=2, num_workers=2)

    assert row["metric"] == "resnet18_e2e_images_per_sec_per_chip_cpu"
    assert row["unit"] == "images/sec/chip"
    assert row["value"] > 0
    assert row["step_ms"] > 0
    assert row["device_prefetch"] == 2
    assert row["input"] == "synthetic"
    # the acceptance evidence: the stager thread, not the timing loop's
    # thread, produced the staged batches
    assert row["staged_batches"] >= 3
    assert row["staged_off_thread"] is True
    # wire-format evidence: the preset default is the uint8 dataplane, and
    # the observed per-step H2D payload is the uint8 arithmetic — 1 B/px
    # images + i32 labels, a ~4× cut vs the float32 wire (4 B/px)
    assert row["input_dtype"] == "uint8"
    uint8_bytes = 16 * 32 * 32 * 3 * 1 + 16 * 4
    float32_bytes = 16 * 32 * 32 * 3 * 4 + 16 * 4
    assert row["h2d_bytes_per_step"] == uint8_bytes
    assert float32_bytes / row["h2d_bytes_per_step"] > 3.9
    # donation-audit evidence (analysis/jaxpr_audit.donation_evidence): the
    # train step's donated state must be FULLY aliased in the executable —
    # the "no step buffer round-trips HBM" claim, carried on the row
    assert row["donated_bytes"] > 10_000_000  # the real resnet18 state
    assert row["aliased_bytes"] == row["donated_bytes"]
    assert row["donation_coverage"] == 1.0

    # dtype evidence from the same AOT window: this smoke pins f32 compute,
    # so the FLOP-weighted bf16 fraction is 0 and the unwaivable numerics
    # contracts (no f64, f32 accumulation/loss head, no round-trip casts)
    # must hold on the exact compiled step
    assert row["bf16_op_fraction"] == 0.0
    assert row["accum_dtype_ok"] is True
    assert row["temp_bytes"] > 0
    # comms/memory evidence from the SAME compile window
    # (analysis/sharding_audit.step_comms_evidence): a dp-sharded train
    # step carries the gradient all-reduce payload, and the executable's
    # peak HBM exceeds the donated state it updates in place
    assert row["collective_bytes_per_step"] > 0
    assert row["peak_hbm_bytes"] > row["donated_bytes"]
    # grad-accum / H2D-overlap schema lock: the defaults report K=1, the
    # per-optimizer-step payload aliases the per-step payload (one
    # optimizer step per compiled program), overlap off, and the
    # consumer-side input wait is measured
    assert row["grad_accum"] == 1
    assert (row["collective_bytes_per_optimizer_step"]
            == row["collective_bytes_per_step"])
    assert row["h2d_overlap"] is False
    assert row["h2d_wait_ms_per_step"] >= 0


def test_bench_row_trace_breakdown_cpu():
    """`--trace` on the device-resident bench row emits a
    `step_breakdown_ms` whose six buckets cover the measured step time —
    the ISSUE's acceptance bound: the bucket sum lands within 15% of the
    row's step_ms (idle is the remainder, so the SpanRecorder layout
    guarantees the per-chunk sum; the 15% slack absorbs chunk-vs-median
    skew). Schema lock for the trace row the worklist captures on TPU."""
    import jax

    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.obs.trace import BUCKETS
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    cfg.data.batch_size = 16
    mesh = meshlib.make_mesh()
    row = bench._bench_row(
        cfg, mesh, steps=2, warmup=1,
        metric="resnet18_train_images_per_sec_per_chip_cpu",
        n_chips=len(jax.devices()), peak=None, trace=True)

    assert row["step_ms"] > 0
    assert row["breakdown_source"] in ("probes", "trace+probes")
    agg = row["step_breakdown_ms"]
    for bucket in BUCKETS:
        assert bucket in agg, bucket
        assert agg[bucket] >= 0
    total = sum(agg[b] for b in BUCKETS)
    assert abs(total - row["step_ms"]) <= 0.15 * row["step_ms"], (
        total, row["step_ms"])
    # the probe decomposition attributes real compute to fwd on any backend
    assert agg["fwd"] > 0


def test_bench_e2e_row_float32_wire_bytes():
    """`--input-dtype float32` (the legacy wire) reports 4 B/px payloads —
    the committed-trajectory comparison row for the ~4× claim. Driven
    through the same `_bench_e2e_row` with a prefetch-0 synchronous pass
    (no second compile path; the row builder reuses the uint8 smoke's
    model shape, so the wire is the only variable)."""
    import jax

    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    cfg.data.batch_size = 16
    cfg.data.input_dtype = "float32"
    mesh = meshlib.make_mesh()
    row = bench._bench_e2e_row(
        cfg, mesh, steps=1, warmup=1,
        metric=bench._e2e_metric_name("resnet18", False, "cpu"),
        n_chips=len(jax.devices()), dataset_kind="synthetic", root="",
        n_images=64, src_size=0, device_prefetch=0, num_workers=1)
    assert row["input_dtype"] == "float32"
    assert row["h2d_bytes_per_step"] == 16 * 32 * 32 * 3 * 4 + 16 * 4


def test_serve_metric_name_schema():
    """Lock the serving row's metric naming: the TPU capture must emit
    exactly `resnet50_serve_latency`, with the standard platform suffix
    off-accel — same convention as the e2e row."""
    import bench

    assert bench._serve_metric_name("resnet50", True, "tpu") == \
        "resnet50_serve_latency"
    assert bench._serve_metric_name("resnet18", False, "cpu") == \
        "resnet18_serve_latency_cpu"


def test_bench_cli_has_serve_flags():
    """The --serve surface must keep parsing (the smoke below drives the
    row builder directly, so argparse drift would otherwise go unseen)."""
    p = subprocess.run([sys.executable, "bench.py", "--help"], cwd=REPO,
                       capture_output=True, timeout=60)
    assert p.returncode == 0, p.stderr[-300:]
    helptext = p.stdout.decode()
    for flag in ("--serve", "--serve-requests", "--serve-rps",
                 "--serve-buckets", "--serve-max-batch", "--serve-timeout-ms"):
        assert flag in helptext, flag


def test_bench_serve_row_smoke_cpu():
    """Run the serving bench path (the exact `_bench_serve_row` that
    `bench.py --serve` calls) on the CPU backend with a tiny model, and
    lock the emitted row's schema: the driver's regression guard keys on
    these fields, and the bucket evidence must prove the compile-count
    bound held."""
    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    # a dp2 serve mesh (conftest forces 8 virtual CPU devices): the row
    # runs the dp-SHARDED predict, and the (2, 4) buckets are already
    # dp-divisible so the requested schema survives the round-up
    mesh = meshlib.serve_mesh(2)
    row = bench._bench_serve_row(
        cfg, mesh, metric=bench._serve_metric_name("resnet18", False, "cpu"),
        n_requests=10, offered_rps=0.0, buckets=(2, 4), max_batch=4,
        timeout_ms=10.0, topk=3)

    assert row["metric"] == "resnet18_serve_latency_cpu"
    assert row["unit"] == "ms"
    assert row["p99_ms"] >= row["p95_ms"] >= row["p50_ms"] > 0
    assert row["requests_per_sec"] > 0
    assert row["n_requests"] == 10 and row["offered_rps"] == 0.0
    assert row["buckets"] == [2, 4] and row["topk"] == 3
    # bucket evidence: only bucket shapes ran (the compile-count bound),
    # and the histogram accounts for every batch
    assert set(row["compiled_buckets"]) <= {2, 4}
    assert row["bucket_hist"] and all(
        int(k) in (2, 4) for k in row["bucket_hist"])
    assert 0 < row["fill_ratio"] <= 1.0
    # replica boot evidence (serve/aot.py): the first engine compiles +
    # banks the bucket executables, the measured engine deserializes them
    # — the warm boot must win, and the hit flag must prove the sidecar
    # (not a shared jit cache) is what made it instant
    assert row["aot_cache_hit"] is True
    assert row["serve_devices"] >= 1
    assert row["cold_start_ms"] > row["warm_start_ms"] > 0


def test_serve_slo_metric_name_schema():
    """Lock the SLO-search row's metric naming: the TPU capture must emit
    exactly `resnet50_max_rps_at_p99_slo`, with the standard platform
    suffix off-accel — same convention as the serve row."""
    import bench

    assert bench._serve_slo_metric_name("resnet50", True, "tpu") == \
        "resnet50_max_rps_at_p99_slo"
    assert bench._serve_slo_metric_name("resnet18", False, "cpu") == \
        "resnet18_max_rps_at_p99_slo_cpu"


def test_bench_cli_has_serve_slo_flags():
    """The SLO-search surface must keep parsing (the smoke below drives
    the row builder directly, so argparse drift would otherwise go
    unseen)."""
    p = subprocess.run([sys.executable, "bench.py", "--help"], cwd=REPO,
                       capture_output=True, timeout=60)
    assert p.returncode == 0, p.stderr[-300:]
    helptext = p.stdout.decode()
    for flag in ("--serve-slo-p99-ms", "--serve-slo-max-rps",
                 "--serve-slo-iters"):
        assert flag in helptext, flag


def test_bench_serve_slo_row_smoke_cpu():
    """Run the closed-loop SLO search (the exact `_bench_serve_slo_row`
    that `bench.py --serve --serve-slo-p99-ms N` calls) on the CPU backend
    with a tiny model, and lock the emitted row's schema. The reported
    value must be a KNOWN-GOOD floor: either 0 (nothing held the SLO) or
    an rps some probe actually sustained — never an extrapolation — and
    the probe ladder must ride along as evidence."""
    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 16
    mesh = meshlib.serve_mesh(2)
    row = bench._bench_serve_slo_row(
        cfg, mesh,
        metric=bench._serve_slo_metric_name("resnet18", False, "cpu"),
        slo_p99_ms=60_000.0,  # generous: CPU smoke proves schema, not perf
        max_rps=32.0, iters=2, n_requests=6,
        buckets=(2, 4), max_batch=4, timeout_ms=5.0, topk=3)

    assert row["metric"] == "resnet18_max_rps_at_p99_slo_cpu"
    assert row["unit"] == "rps"
    assert row["p99_slo_ms"] == 60_000.0
    assert row["slo_bound_rps"] == 32.0
    assert row["n_requests_per_probe"] == 6
    assert row["buckets"] == [2, 4] and row["topk"] == 3
    assert row["serve_devices"] >= 1
    # the probe ladder: every probe carries (rps, p99_ms, ok), the first
    # one is the ceiling probe at max_rps
    assert row["probes"], "no probes recorded"
    assert row["probes"][0]["rps"] == 32.0
    for p_ in row["probes"]:
        assert set(p_) == {"rps", "p99_ms", "ok"}
        assert p_["p99_ms"] > 0
    # value is the highest KNOWN-GOOD rps: it must equal some passing
    # probe's rps (or 0.0 when none passed), and with a 60s SLO on 6
    # requests the ceiling probe passes → bound-limited at max_rps
    passing = [p_["rps"] for p_ in row["probes"] if p_["ok"]]
    assert row["value"] == (max(passing) if passing else 0.0)
    assert row["bound_limited"] is True and row["value"] == 32.0
    assert row["p99_at_max_ms"] > 0


@pytest.mark.slow
def test_bench_e2e_row_accum_overlap_smoke():
    """The K-accumulation + double-buffered-H2D e2e row (`bench.py --e2e
    --grad-accum 4 --h2d-overlap`): one jitted optimizer step scans K=4
    microbatches, the prefetcher pipelines fetch behind the transfer, and
    the row carries the evidence fields the TPU worklist A/B keys on.
    Slow-marked (full e2e boot + a K=4 scan compile): the fast e2e smoke
    above already locks the new row fields at K=1, and the overlap
    thread mechanics are tier-1 in test_device_prefetch.py."""
    import jax

    import bench
    from ddp_classification_pytorch_tpu.config import get_preset
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    cfg.data.batch_size = 32  # dp=8 -> per-replica 4 -> K=4 x mb=1
    cfg.parallel.grad_accum = 4
    mesh = meshlib.make_mesh()
    row = bench._bench_e2e_row(
        cfg, mesh, steps=2, warmup=1,
        metric=bench._e2e_metric_name("resnet18", False, "cpu"),
        n_chips=len(jax.devices()), dataset_kind="synthetic", root="",
        n_images=64, src_size=0, device_prefetch=2, num_workers=2,
        h2d_overlap=True)

    assert row["value"] > 0
    assert row["grad_accum"] == 4
    assert row["h2d_overlap"] is True
    assert row["h2d_wait_ms_per_step"] >= 0
    assert row["staged_off_thread"] is True
    # the accumulated program still reduces gradients (and fully aliases
    # its donated state) ONCE per optimizer step
    assert row["collective_bytes_per_optimizer_step"] > 0
    assert row["donation_coverage"] == 1.0
