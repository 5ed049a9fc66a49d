"""Observability spine (obs/): registry semantics + exposition, atomic
scrape-file rewrite, and the promoted event plane's compat surface (the
span recorder has tests/test_spans.py).

The registry tests pin the operational contracts the instruments are
trusted for: thread-safe counting, quantiles bit-identical to the legacy
ServeMetrics estimator (so `/metrics` and `/metrics.json` can never
disagree about p99), deterministic exposition (golden-testable), and a
`write_prom` a concurrent scraper can read mid-rewrite without ever seeing
a torn file.
"""

import json
import os
import threading

import pytest

from ddp_classification_pytorch_tpu.obs import events as obs_events
from ddp_classification_pytorch_tpu.obs.registry import Registry


# ----------------------------------------------------------------- registry --

def test_counter_concurrent_increments():
    reg = Registry()
    c = reg.counter("t_total", "concurrent counter")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert c.value == 8000


def test_counter_rejects_negative_and_type_mismatch():
    reg = Registry()
    c = reg.counter("a_total", "x")
    with pytest.raises(ValueError):
        c.inc(-1)
    # re-registration with the same kind returns the SAME instrument
    assert reg.counter("a_total", "x") is c
    # ... but a different kind under the same name is a hard error
    with pytest.raises(ValueError):
        reg.gauge("a_total", "x")
    with pytest.raises(ValueError):
        reg.counter("bad name", "x")


def test_gauge_set_inc_dec():
    reg = Registry()
    g = reg.gauge("depth", "x")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4.0


def test_histogram_quantiles_match_legacy_percentile():
    """The registry quantile estimator must be bit-identical to the
    `serve/metrics.py::percentile` the JSON snapshot always reported —
    otherwise /metrics and /metrics.json disagree about the same window."""
    from ddp_classification_pytorch_tpu.serve.metrics import percentile

    reg = Registry()
    h = reg.histogram("lat_ms", "x", window=64)
    data = [float(v) for v in
            [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4]]
    for v in data:
        h.observe(v)
    window = sorted(h.values())
    for q, pct in ((0.5, 50), (0.95, 95), (0.99, 99)):
        assert h.quantile(q) == percentile(window, pct), q
    assert h.count == len(data)
    assert h.sum == sum(data)


def test_histogram_window_is_bounded_but_totals_are_not():
    reg = Registry()
    h = reg.histogram("w_ms", "x", window=4)
    for v in range(10):
        h.observe(float(v))
    assert h.values() == [6.0, 7.0, 8.0, 9.0]  # bounded window
    assert h.count == 10 and h.sum == 45.0     # monotonic all-time totals


def test_exposition_golden():
    """Deterministic exposition: sorted families, one HELP/TYPE block each,
    label escaping, summary shape for histograms."""
    reg = Registry()
    reg.counter("req_total", "requests", labels={"code": "200"}).inc(3)
    reg.counter("req_total", "requests", labels={"code": "503"}).inc()
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_ms", "latency", window=16)
    h.observe(1.0)
    h.observe(3.0)
    assert reg.expose() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_ms latency\n"
        "# TYPE lat_ms summary\n"
        'lat_ms{quantile="0.5"} 1\n'
        'lat_ms{quantile="0.95"} 3\n'
        'lat_ms{quantile="0.99"} 3\n'
        "lat_ms_sum 4\n"
        "lat_ms_count 2\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{code="200"} 3\n'
        'req_total{code="503"} 1\n'
    )


def test_snapshot_maps_samples_to_values():
    reg = Registry()
    reg.counter("a_total", "x").inc(2)
    reg.gauge("g", "x").set(1.5)
    snap = reg.snapshot()
    assert snap["a_total"] == 2
    assert snap["g"] == 1.5


def test_write_prom_atomic_under_concurrent_reads(tmp_path):
    """A scraper reading the file while the writer loops must always see a
    COMPLETE exposition (the final family line present) — torn reads would
    mean os.replace is not being used or the tmp file leaked into place."""
    reg = Registry()
    c = reg.counter("rewrites_total", "x")
    reg.gauge("zz_last", "sentinel family, sorts last").set(1)
    path = str(tmp_path / "metrics.prom")
    reg.write_prom(path)
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            c.inc()
            reg.write_prom(path)

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(300):
            with open(path) as f:
                body = f.read()
            # complete snapshot: ends with the lexicographically-last
            # family's sample line, and the counter line parses
            assert body.endswith("zz_last 1\n"), body[-80:]
            lines = [ln for ln in body.splitlines()
                     if ln.startswith("rewrites_total ")]
            assert len(lines) == 1 and float(lines[0].split()[1]) >= 0
    finally:
        stop.set()
        t.join(timeout=60)
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


@pytest.mark.parametrize("value,text", [
    (float("nan"), "NaN"), (float("inf"), "+Inf"), (float("-inf"), "-Inf")])
def test_a_non_finite_gauge_shows_in_the_scrape_file(tmp_path, value, text):
    """A diverged loss is set on `train_loss` at the log cadence: the scrape
    file must show it as the exposition format spells it, beside the other
    families, and not raise out of `write_prom` into the step loop."""
    reg = Registry()
    reg.gauge("train_loss", "mean train loss").set(value)
    reg.counter("train_steps_total", "steps").inc(3)
    assert f"train_loss {text}\n" in reg.expose()
    path = str(tmp_path / "metrics.prom")
    reg.write_prom(path)
    with open(path) as f:
        body = f.read()
    assert f"train_loss {text}\n" in body and "train_steps_total 3\n" in body


# ------------------------------------------------------------- event plane --

def test_emit_gated_and_readable(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.delenv(obs_events.ENV_EVENTS, raising=False)
    obs_events.emit("swap", epoch=3)  # ungated: must be a no-op
    assert not os.path.exists(path)
    monkeypatch.setenv(obs_events.ENV_EVENTS, path)
    monkeypatch.setenv(obs_events.ENV_SOURCE, "test")
    obs_events.emit("swap", epoch=3)
    (rec,) = obs_events.read_events(path)
    assert rec["kind"] == "swap" and rec["epoch"] == 3
    assert rec["source"] == "test"


# ------------------------------------------------------- serve wire surface --

class _StubEngine:
    """Just enough engine for the HTTP layer: metrics + health attrs."""

    def __init__(self):
        from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics

        self.metrics = ServeMetrics()
        self.queue_depth = 0
        self.closed = False
        self.params_digest = "d" * 8
        self.params_generation = 1


def _get(port, path):
    """One HTTP/1.0 exchange (the stdlib handler closes per response)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.getheader("Content-Type"), r.read().decode()
    finally:
        conn.close()


def test_http_metrics_exposition_and_json(tmp_path):
    """GET /metrics serves Prometheus text exposition (versioned
    Content-Type) carrying at least one counter from each owning family —
    serve_*, engine_*, and the watcher's watcher_* (registered into the
    same registry at construction) — while /metrics.json preserves the
    legacy dict and /healthz stays JSON. The wire-contract acceptance."""
    from ddp_classification_pytorch_tpu.serve.http import make_server
    from ddp_classification_pytorch_tpu.serve.reload import CheckpointWatcher

    engine = _StubEngine()
    engine.metrics.record_submit()
    # constructing the watcher registers the watcher_* family into the
    # engine's registry — no poll thread needed for the exposition
    watcher = CheckpointWatcher(str(tmp_path), engine, template_state=None,
                                metrics=engine.metrics)
    server = make_server(engine, 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        status, ctype, body = _get(port, "/metrics")
        assert status == 200
        assert ctype == "text/plain; version=0.0.4"
        assert "# TYPE serve_requests_total counter" in body
        assert "serve_requests_total 1" in body
        assert "# TYPE engine_batches_total counter" in body
        assert "# TYPE watcher_polls_total counter" in body
        status, ctype, body = _get(port, "/metrics.json")
        assert status == 200 and ctype == "application/json"
        snap = json.loads(body)
        assert snap["requests"] == 1 and "p99_ms" in snap
        status, ctype, body = _get(port, "/healthz")
        assert status == 200 and ctype == "application/json"
        health = json.loads(body)
        assert health["ok"] is True and health["digest"] == "d" * 8
    finally:
        server.shutdown()
        server.server_close()
    assert watcher.alive is False


def test_serve_metrics_registry_bridge_preserves_legacy_snapshot():
    """The instrument-backed ServeMetrics must report the EXACT legacy
    snapshot keys/values (`/healthz` and `/metrics.json` key on them)."""
    from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(latency_window=8)
    m.record_submit()
    m.record_submit()
    m.record_reject()
    m.record_batch(4, 2, [1.0, 2.0])
    m.record_error()
    m.record_reload(ok=True)
    m.record_reload(ok=False)
    m.record_recompile()
    s = m.snapshot(queue_depth=5)
    assert s["requests"] == 2 and s["completed"] == 2 and s["rejected"] == 1
    assert s["batches"] == 1 and s["errors"] == 1
    assert s["reloads"] == 1 and s["reloads_rejected"] == 1
    assert s["recompiles"] == 1
    assert s["bucket_hist"] == {4: 1}
    assert s["fill_ratio"] == 0.5
    assert s["p50_ms"] == 1.0 and s["p99_ms"] == 2.0
    assert s["queue_depth"] == 5
    # and the same numbers exposed through the registry
    exp = m.registry.expose()
    assert "engine_rows_padded_total 2" in exp
    assert 'engine_bucket_batches_total{bucket="4"} 1' in exp
    assert "serve_queue_depth 5" in exp


def test_watcher_instruments_count_polls_and_backoff(tmp_path):
    """The watcher's registry instruments track polls/errors/backoff next
    to the quarantine counter — check_once on an empty dir ticks polls;
    a failing poll sets the backoff gauge; a quiet one resets it."""
    from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics
    from ddp_classification_pytorch_tpu.serve.reload import CheckpointWatcher

    metrics = ServeMetrics()
    w = CheckpointWatcher(str(tmp_path), engine=None, template_state=None,
                          poll_s=0.5, metrics=metrics)
    assert w.poll_once() == 0.5
    snap = metrics.registry.snapshot()
    assert snap["watcher_polls_total"] == 1
    assert snap["watcher_errors_total"] == 0
    assert snap["watcher_backoff_seconds"] == 0

    def boom():
        raise OSError("fs fault")

    w.check_once = boom
    backoff = w.poll_once()
    assert backoff == 1.0  # poll_s * 2^1
    snap = metrics.registry.snapshot()
    assert snap["watcher_errors_total"] == 1
    assert snap["watcher_backoff_seconds"] == 1.0
