"""Serving subsystem (serve/): micro-batching engine, hot-reload, drain.

Everything runs in-process (no sockets) on a tiny resnet18-cifar model —
one module-scoped state + ONE jitted predict shared by every test, so the
bucket programs compile once for the whole file (tier-1 budget: the suite
already outruns its 870 s window; no sleeps beyond the engine's own
~50 ms deadlines).

The acceptance pins:
- concurrent requests through the engine are BIT-identical to the direct
  jitted predict on the same inputs, with at most len(buckets) compiled
  shapes observed;
- a partial batch flushes at the deadline, padded to a bucket, and pad
  rows cannot perturb real rows;
- intake backpressure (bounded queue) rejects loudly;
- hot-reload swaps a newer verified checkpoint and QUARANTINES a corrupt
  candidate while serving continues on the old params;
- SIGTERM drains gracefully: intake stops, queued work completes.
"""

import glob
import json
import os
import signal
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.serve.engine import (
    EngineClosed,
    QueueFull,
    ServingEngine,
)
from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics
from ddp_classification_pytorch_tpu.serve.reload import CheckpointWatcher
from ddp_classification_pytorch_tpu.train.checkpoint import CheckpointManager
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_topk_predict_step

BUCKETS = (2, 4)  # every engine in this module: at most 2 compiled shapes


@pytest.fixture(scope="module")
def sv():
    cfg = get_preset("baseline")
    cfg.model.arch = "resnet18"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.data.num_classes = 8
    cfg.data.image_size = 32
    mesh = meshlib.make_mesh()
    model, _, state = create_train_state(cfg, mesh, steps_per_epoch=1)
    predict = make_topk_predict_step(cfg, model, 3)
    rng = np.random.default_rng(7)
    imgs = rng.integers(0, 256, (8, 32, 32, 3)).astype(np.uint8)
    return SimpleNamespace(cfg=cfg, mesh=mesh, model=model, state=state,
                           predict=predict, imgs=imgs)


def _engine(sv, **kw):
    kw.setdefault("image_size", 32)
    kw.setdefault("input_dtype", "uint8")
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_timeout_ms", 40.0)
    kw.setdefault("queue_depth", 16)
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("metrics", ServeMetrics())
    return ServingEngine(sv.state, sv.predict, **kw)


def test_concurrent_requests_bit_identical_to_direct_predict(sv):
    """4 requests submitted concurrently batch into ONE full micro-batch
    (max_batch=4, deadline generous) and each response is bit-identical to
    the direct jitted predict on the same 4 images stacked as one batch —
    the engine adds batching, not numerics. Compile-count bound: only
    bucket shapes ran, and the jit cache holds at most len(buckets)."""
    engine = _engine(sv, batch_timeout_ms=2000.0).start()
    try:
        futures = [None] * 4
        threads = [threading.Thread(target=lambda i=i: futures.__setitem__(
            i, engine.submit(sv.imgs[i]))) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        preds = [f.result(timeout=30) for f in futures]
    finally:
        engine.drain()

    scores, indices = sv.predict(sv.state, np.stack(sv.imgs[:4]))
    scores, indices = np.asarray(scores), np.asarray(indices)
    for i, p in enumerate(preds):
        np.testing.assert_array_equal(p.indices, indices[i])
        np.testing.assert_array_equal(p.scores, scores[i])  # bitwise
        assert p.latency_ms > 0
    assert engine.seen_buckets == {4}
    cache = engine.compiled_programs()
    assert cache is None or cache <= len(BUCKETS)
    assert engine.metrics.snapshot()["fill_ratio"] == 1.0


def test_deadline_flushes_partial_batch(sv):
    """3 requests < max_batch must NOT wait forever: the batcher flushes at
    batch_timeout_ms, padded to the smallest covering bucket (4), and the
    fill accounting records 3 real + 1 pad row."""
    metrics = ServeMetrics()
    engine = _engine(sv, batch_timeout_ms=50.0, metrics=metrics).start()
    try:
        futures = [engine.submit(sv.imgs[i]) for i in range(3)]
        preds = [f.result(timeout=30) for f in futures]
    finally:
        engine.drain()
    assert len(preds) == 3 and all(p.indices.shape == (3,) for p in preds)
    snap = metrics.snapshot()
    assert snap["bucket_hist"] == {4: 1}
    assert snap["fill_ratio"] == 0.75  # 3 real rows of a 4-row bucket
    assert snap["p99_ms"] >= snap["p50_ms"] > 0


def test_bucket_padding_does_not_leak_into_real_rows(sv):
    """Validity of the pad scheme: the same image answered alone (1 real +
    1 pad row in bucket 2) and answered next to OTHER traffic (2 real rows,
    same bucket program) must produce bitwise-identical results — pad rows
    are dead weight, not numerics."""
    alone = _engine(sv)
    f = alone.submit(sv.imgs[0])
    assert alone.process_once() == 1  # in-process drive: no thread needed
    p_alone = f.result(timeout=30)
    assert alone.seen_buckets == {2}

    paired = _engine(sv)
    f0 = paired.submit(sv.imgs[0])
    paired.submit(sv.imgs[1])
    assert paired.process_once() == 2
    p_paired = f0.result(timeout=30)

    np.testing.assert_array_equal(p_alone.indices, p_paired.indices)
    np.testing.assert_array_equal(p_alone.scores, p_paired.scores)


def test_queue_full_backpressure(sv):
    """Intake is bounded: queue_depth submits are accepted, the next raises
    QueueFull immediately (no silent latency growth) and is counted; the
    accepted requests still complete on flush."""
    metrics = ServeMetrics()
    engine = _engine(sv, queue_depth=2, metrics=metrics)
    f1, f2 = engine.submit(sv.imgs[0]), engine.submit(sv.imgs[1])
    with pytest.raises(QueueFull):
        engine.submit(sv.imgs[2])
    assert metrics.snapshot()["rejected"] == 1
    engine.drain()  # no thread: drain flushes inline
    assert f1.result(timeout=30).indices.shape == (3,)
    assert f2.result(timeout=30).indices.shape == (3,)
    with pytest.raises(EngineClosed):
        engine.submit(sv.imgs[0])


def test_submit_validates_wire_contract(sv):
    """A mis-shaped or mis-dtyped request fails AT SUBMIT (per-request),
    never inside a shared padded batch at jit time."""
    engine = _engine(sv)
    with pytest.raises(ValueError):
        engine.submit(sv.imgs[0].astype(np.float32))  # wrong wire dtype
    with pytest.raises(ValueError):
        engine.submit(np.zeros((16, 16, 3), np.uint8))  # wrong shape


def test_hot_reload_swaps_and_quarantines_corrupt(sv, tmp_path):
    """A newer verified checkpoint hot-swaps between batches (responses
    change to the new params' outputs, bitwise); a newer-still CORRUPT
    candidate is quarantined (*.corrupt) and serving continues on the last
    verified params."""
    import jax

    run_dir = str(tmp_path)
    mgr = CheckpointManager(run_dir, async_save=False)
    state2 = sv.state.replace(params=jax.tree_util.tree_map(
        lambda x: x * 1.5, sv.state.params))
    mgr.save(state2, epoch=1)

    metrics = ServeMetrics()
    engine = _engine(sv, metrics=metrics)
    watcher = CheckpointWatcher(run_dir, engine, sv.state, metrics=metrics)

    base_scores = np.asarray(sv.predict(sv.state, np.stack(sv.imgs[:2]))[0])
    assert watcher.check_once() is True
    assert watcher.loaded_epoch == 1
    f = engine.submit(sv.imgs[0])
    engine.submit(sv.imgs[1])
    assert engine.process_once() == 2
    got = f.result(timeout=30)
    # the swap took: responses now match the RELOADED params, not the old
    reload_scores = np.asarray(
        sv.predict(engine._state, np.stack(sv.imgs[:2]))[0])
    np.testing.assert_array_equal(got.scores, reload_scores[0])
    assert not np.array_equal(got.scores, base_scores[0])

    # corrupt newer candidate: epoch-2 bytes torn after the sidecar landed
    mgr.save(state2, epoch=2)
    with open(mgr.epoch_path(2), "r+b") as fh:
        fh.seek(100)
        fh.write(b"\xde\xad\xbe\xef")
    assert watcher.check_once() is False  # nothing newer verified
    assert os.path.exists(mgr.epoch_path(2) + ".corrupt")
    assert not os.path.exists(mgr.epoch_path(2))
    assert watcher.loaded_epoch == 1  # still serving the verified params
    snap = metrics.snapshot()
    assert snap["reloads"] == 1 and snap["reloads_rejected"] == 1
    # and the engine still answers (on the epoch-1 params)
    f = engine.submit(sv.imgs[2])
    assert engine.process_once() == 1
    np.testing.assert_array_equal(
        f.result(timeout=30).scores,
        np.asarray(sv.predict(engine._state, np.stack(sv.imgs[2:4]))[0])[0])


def test_swap_racing_drain_never_mixes_params_in_a_batch(sv):
    """swap_state storms from a reloader thread while requests flow and the
    engine finally drains: every answered Prediction must be INTERNALLY
    consistent — its scores bitwise-equal to the direct predict under the
    params its digest names. A batch that adopted new params mid-flight
    (mixing two checkpoints inside one micro-batch) would answer with one
    digest and the other params' numerics, and fail the bitwise check."""
    import jax

    img = sv.imgs[0]
    state_b = sv.state.replace(params=jax.tree_util.tree_map(
        lambda x: x * 1.5, sv.state.params))
    # expected rows per digest at every bucket shape a batch might run;
    # "A" republishes the init params under a named digest, so A/fresh
    # share numerics while B's differ — only B-vs-(A|fresh) mixing exists
    expected = {}
    for name, st in (("fresh", sv.state), ("A", sv.state), ("B", state_b)):
        rows = set()
        for b in BUCKETS:
            out = np.asarray(sv.predict(st, np.stack([img] * b))[0])
            rows.update(out[i].tobytes() for i in range(b))
        expected[name] = rows

    engine = _engine(sv, batch_timeout_ms=5.0, queue_depth=32).start()
    stop = threading.Event()

    def swapper():
        flip = False
        while not stop.is_set():
            if flip:
                engine.swap_state(state_b, digest="B", generation=2)
            else:
                engine.swap_state(sv.state, digest="A", generation=1)
            flip = not flip
            time.sleep(0.002)

    t = threading.Thread(target=swapper)
    t.start()
    futures = []
    try:
        for _ in range(24):
            try:
                futures.append(engine.submit(img))
            except QueueFull:
                pass
            time.sleep(0.003)
        # drain races the still-running swapper: the inline flush must keep
        # the one-params-version-per-batch contract too
        engine.drain()
    finally:
        stop.set()
        t.join(timeout=60)
    preds = [f.result(timeout=30) for f in futures]
    assert preds, "no request was ever accepted"
    for p in preds:
        assert p.digest in expected
        assert p.scores.tobytes() in expected[p.digest], (
            f"scores answered under digest {p.digest!r} do not match that "
            "checkpoint's params — a micro-batch mixed two param versions")


def test_quarantine_double_rename_yields_exactly_one_corrupt(sv, tmp_path):
    """The shared-run-dir race: the serving watcher AND a trainer-side
    manager both find the same corrupt candidate and quarantine it. In
    either order the loser's rename must be a silent no-op — the pod ends
    with exactly ONE *.corrupt file, no crash, serving state untouched."""

    def corrupt_candidate(run_dir, epoch):
        mgr = CheckpointManager(run_dir, async_save=False)
        mgr.save(sv.state, epoch=epoch)
        with open(mgr.epoch_path(epoch), "r+b") as fh:
            fh.seek(100)
            fh.write(b"\xde\xad\xbe\xef")
        return mgr

    stub = SimpleNamespace(swap_state=lambda *a, **k: None)

    # order 1: the trainer-side manager quarantines first
    d1 = str(tmp_path / "a")
    mgr = corrupt_candidate(d1, 1)
    watcher = CheckpointWatcher(d1, stub, sv.state)
    assert mgr.restore_verified(sv.state, mgr.epoch_path(1)) is None
    assert watcher.check_once() is False  # nothing left to scan; no crash
    assert watcher.loaded_epoch == -1
    assert len(glob.glob(os.path.join(d1, "*.msgpack.corrupt"))) == 1

    # order 2: the watcher quarantines first, the manager loses the race
    d2 = str(tmp_path / "b")
    mgr = corrupt_candidate(d2, 1)
    watcher = CheckpointWatcher(d2, stub, sv.state)
    assert watcher.check_once() is False
    assert mgr.restore_verified(sv.state, mgr.epoch_path(1)) is None
    # and a second rename of the SAME path (both sides committed to
    # quarantine before either rename landed) is a no-op, not a crash
    mgr._quarantine(mgr.epoch_path(1), "sha256 mismatch")
    assert len(glob.glob(os.path.join(d2, "*.msgpack.corrupt"))) == 1
    assert watcher.loaded_epoch == -1


def test_http_healthz_and_retry_after(sv, tmp_path):
    """The wire contract of serve/http.py: /healthz reports params
    provenance + watcher liveness, queue-full answers 503 busy with
    Retry-After 1 (same replica, soon), draining answers 503 draining with
    Retry-After 5 (go elsewhere) — the distinction S2 relies on."""
    import io
    import urllib.request
    from urllib.error import HTTPError

    from PIL import Image

    from ddp_classification_pytorch_tpu.serve.http import make_server

    buf = io.BytesIO()
    Image.fromarray(sv.imgs[0]).save(buf, format="PNG")
    png = buf.getvalue()

    engine = _engine(sv, queue_depth=1,
                     transform=lambda img, rng: sv.imgs[0])
    watcher = CheckpointWatcher(str(tmp_path), engine, sv.state, poll_s=0.2)
    server = make_server(engine, 0, watcher=watcher)  # 0 = ephemeral port
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.loads(r.read())

    def post():
        req = urllib.request.Request(base + "/predict", data=png,
                                     method="POST")
        return urllib.request.urlopen(req, timeout=30)

    try:
        health = get("/healthz")
        assert health["ok"] is True
        assert health["digest"] == "fresh" and health["generation"] == -1
        assert health["watcher_alive"] is False  # built but never started
        watcher.start()
        assert get("/healthz")["watcher_alive"] is True

        # bounded queue full (batcher not running) → 503 busy + hint
        engine.submit(sv.imgs[0])
        with pytest.raises(HTTPError) as exc:
            post()
        assert exc.value.code == 503
        assert exc.value.headers["Retry-After"] == "1"
        assert json.loads(exc.value.read())["state"] == "busy"

        engine.start()
        with post() as r:
            body = json.loads(r.read())
        assert body["digest"] == "fresh" and body["generation"] == -1
        assert len(body["topk"]) == 3

        engine.drain()
        with pytest.raises(HTTPError) as exc:
            post()
        assert exc.value.code == 503
        assert exc.value.headers["Retry-After"] == "5"
        assert json.loads(exc.value.read())["state"] == "draining"
        assert get("/healthz")["ok"] is False
    finally:
        watcher.stop()
        server.shutdown()
        server.server_close()


def test_sigterm_drains_gracefully(sv):
    """The cli.serve signal contract, in-process: SIGTERM sets the drain
    event; drain stops intake (EngineClosed), answers everything already
    queued, and joins the batcher — no request accepted before the signal
    is ever dropped."""
    from ddp_classification_pytorch_tpu.cli.serve import _install_signal_handlers

    stop = threading.Event()
    prev = _install_signal_handlers(stop)
    engine = _engine(sv, batch_timeout_ms=20.0).start()
    try:
        futures = [engine.submit(sv.imgs[i]) for i in range(3)]
        os.kill(os.getpid(), signal.SIGTERM)
        assert stop.wait(timeout=5.0), "SIGTERM handler did not fire"
        engine.drain()
        for f in futures:
            assert f.result(timeout=30).indices.shape == (3,)
        with pytest.raises(EngineClosed):
            engine.submit(sv.imgs[0])
    finally:
        for sig, handler in prev.items():
            signal.signal(sig, handler)


def test_drain_flushes_requests_queued_after_batcher_stopped(sv):
    """Requests still in the queue when drain begins (engine never started
    — the worst case) are all answered before drain returns."""
    engine = _engine(sv)
    futures = [engine.submit(sv.imgs[i]) for i in range(5)]
    t0 = time.monotonic()
    engine.drain()
    assert time.monotonic() - t0 < 30
    assert all(f.done() for f in futures)
    assert all(f.result().indices.shape == (3,) for f in futures)


# ------------------------------------------------------- dp-sharded serve --


def test_resolve_buckets_dp_arithmetic():
    """The dp bucket contract (docs/serving.md): explicit buckets that
    cannot shard evenly over 'data' are a config error (the operator asked
    for shapes that cannot run — rc 2 at the CLI), while auto-buckets
    round UP to the next dp multiple and dedup."""
    from ddp_classification_pytorch_tpu.config import (
        ServeConfig,
        dp_round_up_buckets,
    )

    assert ServeConfig(max_batch=8).resolve_buckets(2) == (2, 4, 8)
    assert ServeConfig(max_batch=8).resolve_buckets(1) == (1, 2, 4, 8)
    assert dp_round_up_buckets((1, 3, 4), 4) == (4,)
    assert dp_round_up_buckets((1, 5), 4) == (4, 8)

    explicit = ServeConfig(buckets=(1, 3), max_batch=3)
    assert explicit.resolve_buckets(1) == (1, 3)
    with pytest.raises(ValueError, match="serve-bucket-dp-indivisible"):
        explicit.resolve_buckets(2)


def test_engine_rejects_dp_indivisible_buckets(sv):
    """The same fence at engine construction: a bucket the mesh cannot
    shard must fail loudly at build time, never at assembly time inside a
    live micro-batch."""
    mesh = meshlib.serve_mesh(2)
    with pytest.raises(ValueError, match="serve-bucket-dp-indivisible"):
        ServingEngine(sv.state, sv.predict, image_size=32,
                      input_dtype="uint8", max_batch=3, buckets=(1, 3),
                      mesh=mesh)


def test_dp_sharded_engine_matches_direct_predict(sv):
    """Numerics fence for the tentpole: a dp2 engine (padded batches
    assembled as data-sharded global arrays, dp-sharded predict) answers
    with the SAME top-k indices as the single-device jitted predict and
    scores equal to float tolerance — sharding adds communication, not
    numerics."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = meshlib.serve_mesh(2)
    # the module state lives on the full 8-device mesh; a serving replica
    # holds its params replicated over ITS OWN mesh
    state = jax.device_put(sv.state, NamedSharding(mesh, PartitionSpec()))
    predict_dp = make_topk_predict_step(sv.cfg, sv.model, 3, mesh=mesh)
    engine = ServingEngine(state, predict_dp, image_size=32,
                           input_dtype="uint8", max_batch=4,
                           batch_timeout_ms=40.0, queue_depth=16,
                           buckets=BUCKETS, metrics=ServeMetrics(),
                           mesh=mesh)
    assert engine.dp == 2 and engine.serve_devices == 2
    futures = [engine.submit(sv.imgs[i]) for i in range(4)]
    assert engine.process_once() == 4
    preds = [f.result(timeout=30) for f in futures]

    scores, indices = sv.predict(sv.state, np.stack(sv.imgs[:4]))
    scores, indices = np.asarray(scores), np.asarray(indices)
    for i, p in enumerate(preds):
        np.testing.assert_array_equal(p.indices, indices[i])
        np.testing.assert_allclose(p.scores, scores[i], rtol=1e-5, atol=1e-6)
    assert engine.seen_buckets == {4}


# ------------------------------------------------------------- cli.serve --


def _serve_main_rc(argv, capsys):
    from ddp_classification_pytorch_tpu.cli.serve import main

    with pytest.raises(SystemExit) as exc:
        main(argv)
    return exc.value.code, capsys.readouterr().err


def test_cli_serve_config_errors_exit_2(capsys):
    """Deterministic knob errors exit rc 2 BEFORE any backend work — the
    same discipline as cli.train, so supervisors never replay them."""
    # max_batch beyond the largest bucket: no shape could run a full batch
    rc, err = _serve_main_rc(
        ["baseline", "--ckpt", "/tmp/x.msgpack", "--max_batch", "16",
         "--buckets", "1,2,4"], capsys)
    assert rc == 2 and "config error" in err
    # no weights source at all
    rc, err = _serve_main_rc(["baseline"], capsys)
    assert rc == 2 and "config error" in err
    # topk cannot exceed the class count
    rc, err = _serve_main_rc(
        ["baseline", "--ckpt", "/tmp/x.msgpack", "--num_classes", "4",
         "--topk", "9"], capsys)
    assert rc == 2 and "config error" in err


def test_cli_serve_selfcheck_smoke(tmp_path, capsys):
    """The socket-free end-to-end path: cli.serve --selfcheck builds the
    model, warms every bucket, serves synthetic requests through the real
    batcher thread, drains, and returns cleanly (rc 0)."""
    from ddp_classification_pytorch_tpu.cli.serve import main

    # conftest forces 8 CPU devices; --serve_devices 2 keeps the explicit
    # (2,4) buckets dp-divisible AND makes selfcheck exercise the
    # dp-sharded predict end to end
    main(["baseline", "--model", "resnet18", "--variant", "cifar",
          "--dtype", "float32", "--num_classes", "8", "--image_size", "32",
          "--buckets", "2,4", "--max_batch", "4", "--batch_timeout_ms", "20",
          "--serve_devices", "2",
          "--selfcheck", "5", "--platform", "cpu", "--out", str(tmp_path)])
    out = capsys.readouterr().out
    assert "selfcheck ok: 5 requests" in out
    assert "[serve]" in out and "p50=" in out
