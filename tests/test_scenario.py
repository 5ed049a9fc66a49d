"""Scenario subsystem (scenario/): spec grammar, event log, serve-side
chaos kinds, watcher backoff, and — the point — each S1–S4 invariant
checker proven to FIRE on a violating synthetic timeline and pass on a
clean one. The full supervised drill (elastic pod + replicas + load) runs
as the `slow` test at the bottom; everything else is tier-1-lean: no
subprocesses, no sleeps beyond the watcher's own sub-second backoff.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from ddp_classification_pytorch_tpu.obs import events as ev
from ddp_classification_pytorch_tpu.scenario.invariants import (
    check_invariants,
    check_restarts_log,
    check_s1_verified_serve,
    check_s2_availability,
    check_s3_adoption,
    check_s4_analyzer,
    check_s5_fleet,
    good_publishes,
    replica_retire_times,
)
from ddp_classification_pytorch_tpu.scenario.spec import SpecError, load_spec
from ddp_classification_pytorch_tpu.utils.chaos import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ spec --


def test_spec_defaults_and_full_parse(tmp_path):
    s = load_spec("{}")
    assert s.trainer.hosts == 2 and s.serve.replicas == 2
    assert s.availability.floor == 0.5 and s.adopt_deadline_s == 120.0

    full = {
        "trainer": {"hosts": 2, "epochs": 4, "min_processes": 1,
                    "fault_specs": {"0": "ckpt_io@epoch=0",
                                    "1": "host_lost@step=10"}},
        "serve": {"replicas": 2, "poll_s": 0.5,
                  "fault_specs": {"1": "watcher_io@poll=3"}},
        "load": {"rps": 2.0, "timeout_s": 10},
        "availability": {"floor": 0.8, "window_s": 5, "min_samples": 2},
        "adopt_deadline_s": 60,
        "timeline": [{"at": "publish:1", "action": "drain_replica",
                      "replica": 1},
                     {"at": "t:30", "action": "kill_replica"}],
    }
    p = tmp_path / "spec.json"
    p.write_text(json.dumps(full))
    s = load_spec(str(p))  # file path form
    assert s.trainer.fault_specs == {0: "ckpt_io@epoch=0",
                                     1: "host_lost@step=10"}
    assert s.serve.fault_specs == {1: "watcher_io@poll=3"}
    assert [(t.at_kind, t.at_value, t.action, t.replica)
            for t in s.timeline] == [("publish", 1, "drain_replica", 1),
                                     ("t", 30, "kill_replica", 0)]


@pytest.mark.parametrize("bad", [
    "",                                           # empty
    "/nonexistent/spec.json",                     # missing file
    '{"trainer": "x"}',                           # wrong type
    '{"bogus": 1}',                               # unknown key
    '{"trainer": {"hosts": 0}}',                  # out of range
    '{"trainer": {"min_processes": 3}}',          # > hosts
    '{"serve": {"replicas": 0}}',                 # no one to answer
    '{"availability": {"floor": 1.5}}',           # floor out of (0,1]
    '{"adopt_deadline_s": -1}',                   # negative deadline
    '{"trainer": {"fault_specs": {"0": "frobnicate@step=1"}}}',  # bad kind
    '{"trainer": {"fault_specs": {"9": "ckpt_io@epoch=0"}}}',    # bad index
    '{"serve": {"fault_specs": {"0": "watcher_io@step=3"}}}',    # bad unit
    '{"timeline": [{"at": "epoch:1", "action": "drain_replica"}]}',
    '{"timeline": [{"at": "t:1", "action": "explode"}]}',
    '{"timeline": [{"at": "t:1", "action": "drain_replica", "replica": 7}]}',
    '{"serve": {"replicas": 2, "max_replicas": 1}}',  # cap below floor
    '{"serve": {"fleet_ttl_s": 0}}',                  # dead-on-arrival leases
    '{"serve": {"admission_deadline_ms": -1}}',       # negative deadline
    '{"serve": {"scale_out_deadline_s": 0}}',         # zero SLA
    '{"timeline": [{"at": "t:1", "action": "spike_load"}]}',       # no rps
    '{"timeline": [{"at": "t:1", "action": "spike_load", "rps": 0}]}',
    '{"timeline": [{"at": "t:1", "action": "spike_load", "rps": "x"}]}',
    '{"timeline": [{"at": "publish:1", "action": "spike_load", "rps": 2}]}',
    '{"timeline": [{"at": "t:1", "action": "spike_load", "rps": 2, '
    '"replica": 0}]}',
    '{"timeline": [{"at": "t:1", "action": "kill_replica", "rps": 2}]}',
    '{"timeline": [{"at": "t:1", "action": "kill_replica_during_wave", '
    '"replica": 1}]}',
])
def test_spec_errors(bad):
    with pytest.raises(SpecError):
        load_spec(bad)


def test_spec_fleet_keys_and_new_actions_parse():
    s = load_spec(json.dumps({
        "serve": {"replicas": 2, "max_replicas": 3, "fleet_ttl_s": 2.5,
                  "admission_deadline_ms": 250.0,
                  "scale_out_deadline_s": 30.0},
        "timeline": [{"at": "t:30", "action": "spike_load", "rps": 12},
                     {"at": "t:40", "action": "kill_replica_during_wave"}],
    }))
    assert s.serve.max_replicas == 3
    assert s.serve.fleet_ttl_s == 2.5
    assert s.serve.admission_deadline_ms == 250.0
    assert s.serve.scale_out_deadline_s == 30.0
    assert str(s.timeline[0]) == "spike_load@t:30(rps=12.0)"
    assert str(s.timeline[1]) == "kill_replica_during_wave@t:40(holder)"


def test_cli_scenario_bad_spec_exits_2(capsys):
    from ddp_classification_pytorch_tpu.cli.scenario import main

    with pytest.raises(SystemExit) as exc:
        main(["--scenario_spec", '{"bogus": 1}', "--check_only"])
    assert exc.value.code == 2
    assert "spec error" in capsys.readouterr().err


# ----------------------------------------------------- serve-side chaos --


def test_new_fault_kinds_parse_and_validate():
    plan = FaultPlan.parse("publish_corrupt@epoch=2,watcher_io@poll=3")
    assert len(plan.faults) == 2
    with pytest.raises(ValueError):
        FaultPlan.parse("publish_corrupt@step=1")  # epoch-keyed only
    with pytest.raises(ValueError):
        FaultPlan.parse("watcher_io@epoch=1")  # poll-keyed only
    with pytest.raises(ValueError):
        FaultPlan.parse("nan_loss@poll=1")  # poll belongs to watcher_io


def test_watcher_io_fires_once():
    plan = FaultPlan.parse("watcher_io@poll=2")
    plan.maybe_fail_watcher_poll(poll=1)  # below range: no fire
    with pytest.raises(OSError):
        plan.maybe_fail_watcher_poll(poll=2)
    plan.maybe_fail_watcher_poll(poll=2)  # one-shot: consumed


def test_publish_corrupt_tears_published_candidate(tmp_path, monkeypatch):
    """publish_corrupt tears the landed epoch file exactly like ckpt_io
    (sidecar stays from the intact bytes, so verification fails) and the
    publish + publish_torn events land in the armed event log."""
    from ddp_classification_pytorch_tpu.train.checkpoint import (
        CheckpointManager,
    )

    events_path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(ev.ENV_EVENTS, events_path)
    monkeypatch.setenv(ev.ENV_SOURCE, "trainer.h0")
    plan = FaultPlan.parse("publish_corrupt@epoch=0")
    mgr = CheckpointManager(str(tmp_path), async_save=False, chaos=plan)
    state = {"w": np.arange(16, dtype=np.float32)}
    mgr.save(state, epoch=0)

    assert mgr.verify_checkpoint(mgr.epoch_path(0)) == "corrupt"
    recs = ev.read_events(events_path)
    kinds = [r["kind"] for r in recs]
    assert kinds == ["publish", "publish_torn"]
    assert recs[0]["epoch"] == 0 and recs[0]["source"] == "trainer.h0"
    assert len(recs[0]["digest"]) == 64

    # a verifier quarantines it — and the quarantine event lands too
    assert mgr.restore_verified(state, mgr.epoch_path(0)) is None
    assert os.path.exists(mgr.epoch_path(0) + ".corrupt")
    assert ev.read_events(events_path)[-1]["kind"] == "quarantine"


# -------------------------------------------------------------- events --


def test_event_log_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = ev.EventLog(path, "supervisor")
    log.emit("scenario_start", out="x")
    log.emit("publish", epoch=0, digest="d")
    with open(path, "a") as f:
        f.write('{"kind": "swap", "ts": 99')  # producer SIGKILLed mid-append
    recs = ev.read_events(path)
    assert [r["kind"] for r in recs] == ["scenario_start", "publish"]
    assert all(r["source"] == "supervisor" for r in recs)
    assert recs[0]["ts"] <= recs[1]["ts"]


def test_emit_is_noop_without_env(tmp_path, monkeypatch):
    monkeypatch.delenv(ev.ENV_EVENTS, raising=False)
    ev.emit("publish", epoch=0)  # must not write anywhere or raise
    monkeypatch.setenv(ev.ENV_EVENTS, str(tmp_path / "e.jsonl"))
    monkeypatch.setenv(ev.ENV_SOURCE, "t")
    ev.emit("publish", epoch=0)
    assert len(ev.read_events(str(tmp_path / "e.jsonl"))) == 1


# ------------------------------------------------ watcher poll hardening --


class _StubEngine:
    def __init__(self):
        self.swaps = []

    def swap_state(self, state, digest="", generation=-1):
        self.swaps.append((digest, generation))


def test_watcher_poll_backoff_is_bounded_deterministic_and_rearms(tmp_path):
    """Transient fs errors during the poll must not kill the watcher: each
    failure doubles the delay (bounded by max_backoff_s), and the next
    clean poll resets it — the exact sequence is pinned."""
    from ddp_classification_pytorch_tpu.serve.reload import CheckpointWatcher

    plan = FaultPlan.parse(
        "watcher_io@poll=1,watcher_io@poll=2,watcher_io@poll=3,"
        "watcher_io@poll=4,watcher_io@poll=5,watcher_io@poll=6")
    w = CheckpointWatcher(str(tmp_path), _StubEngine(), template_state=None,
                          poll_s=1.0, chaos=plan, max_backoff_s=8.0)
    delays = [w.poll_once() for _ in range(7)]
    # 6 failures: 2,4,8,8,8,8 (capped) — then the clean poll re-arms to 1
    assert delays == [2.0, 4.0, 8.0, 8.0, 8.0, 8.0, 1.0]
    assert w.consecutive_errors == 0 and w.last_error is None
    assert w.polls == 7


def test_watcher_thread_survives_poll_fault_and_stays_alive(tmp_path):
    """The poll THREAD re-arms after an injected EIO: it keeps polling
    (counter advances past the fault) and `alive` stays True — a dead
    watcher may never be silent."""
    from ddp_classification_pytorch_tpu.serve.reload import CheckpointWatcher

    plan = FaultPlan.parse("watcher_io@poll=2")
    w = CheckpointWatcher(str(tmp_path), _StubEngine(), template_state=None,
                          poll_s=0.05, chaos=plan, max_backoff_s=0.1)
    w.start()
    try:
        import time

        deadline = time.monotonic() + 5.0
        while w.polls < 4 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert w.polls >= 4, "watcher thread stopped polling after the fault"
        assert w.alive
    finally:
        w.stop()
    assert not w.alive


# ------------------------------------------------------ invariant FIREs --


def _clean_timeline():
    E = []

    def mk(ts, kind, src, **kw):
        E.append({"ts": ts, "kind": kind, "source": src, **kw})

    mk(0.0, "scenario_start", "supervisor")
    for r in ("replica0", "replica1"):
        mk(1.0, "serve_ready", r, port=1, epoch=-1)
    mk(5.0, "publish", "trainer.h0", epoch=0, path="c0", digest="D0",
       world_size=2)
    for r in ("replica0", "replica1"):
        mk(6.0, "verify_ok", r, epoch=0, path="c0", digest="D0")
        mk(6.1, "swap", r, epoch=0, digest="D0")
    # a TORN publish (epoch 1) that was quarantined: exempt from S3
    mk(8.0, "publish", "trainer.h0", epoch=1, path="c1", digest="D1",
       world_size=2)
    mk(8.0, "publish_torn", "trainer.h0", epoch=1, path="c1")
    mk(9.0, "quarantine", "replica0", path="c1", reason="checksum mismatch")
    for i in range(20):
        ts = 3.0 + i
        status = "busy" if i == 7 else "ok"  # one 503 is degraded-but-alive
        if status != "ok":
            kw = {"code": 503}
        elif ts < 6.0:  # pre-adoption answers on init params: S1-exempt
            kw = {"digest": "fresh", "generation": -1}
        else:
            kw = {"digest": "D0", "generation": 0}
        mk(ts, "request", "loadgen", status=status,
           replica=f"replica{i % 2}", **kw)
    mk(30.0, "lint", "supervisor", rc=0)
    mk(31.0, "scenario_end", "supervisor", ok=True)
    return sorted(E, key=lambda r: r["ts"])


def _spec():
    return load_spec('{"availability": {"floor": 0.5, "window_s": 10.0, '
                     '"min_samples": 3}, "adopt_deadline_s": 20}')


def test_clean_timeline_passes_all_invariants():
    assert check_invariants(_clean_timeline(), _spec()) == []


def test_good_publishes_excludes_torn_and_quarantined():
    goods = good_publishes(_clean_timeline())
    assert [g["epoch"] for g in goods] == [0]


def test_good_publishes_clean_rewrite_of_condemned_path_counts():
    """Fuzzer-found checker bug: condemnation is per WRITE, not per path
    forever. A restart that re-publishes a previously-torn path with a
    clean write must make that publish good again — the old path-set
    implementation silently masked S3/S5(b) on every re-published path
    (regression corpus: tests/data/scenarios/torn-republish-quarantine)."""
    E = [
        {"ts": 1.0, "kind": "publish", "source": "trainer.h0", "epoch": 1,
         "path": "c1", "digest": "TORN", "world_size": 1},
        {"ts": 1.1, "kind": "publish_torn", "source": "trainer.h0",
         "epoch": 1, "path": "c1"},
        {"ts": 2.0, "kind": "quarantine", "source": "replica0", "path": "c1",
         "reason": "checksum mismatch"},
        # restart rewrites the SAME path cleanly
        {"ts": 5.0, "kind": "publish", "source": "trainer.h0", "epoch": 1,
         "path": "c1", "digest": "CLEAN", "world_size": 1},
    ]
    goods = good_publishes(E)
    assert [g["digest"] for g in goods] == ["CLEAN"]
    # and a quarantine AFTER the rewrite condemns only the rewrite
    E.append({"ts": 6.0, "kind": "quarantine", "source": "replica0",
              "path": "c1", "reason": "checksum mismatch"})
    assert good_publishes(E) == []


def test_s1_fires_on_unverified_digest_serve():
    E = _clean_timeline()
    # replica1 answers with a digest only replica0 verified — cross-replica
    # verification does NOT count (each replica attests its own params)
    E.append({"ts": 25.0, "kind": "request", "source": "loadgen",
              "status": "ok", "replica": "replica1", "digest": "DEVIL",
              "generation": 9})
    v = check_s1_verified_serve(E)
    assert len(v) == 1 and v[0].invariant == "S1"
    assert "never verified" in v[0].message


def test_s1_fires_on_missing_digest():
    E = _clean_timeline()
    E.append({"ts": 25.0, "kind": "request", "source": "loadgen",
              "status": "ok", "replica": "replica0", "digest": None})
    assert any("no params digest" in v.message
               for v in check_s1_verified_serve(E))


def test_s2_fires_on_availability_dip():
    E = _clean_timeline()
    for i in range(8):  # a window of connection-refused: fleet dead
        E.append({"ts": 40.0 + i, "kind": "request", "source": "loadgen",
                  "status": "refused", "replica": "-"})
    v = check_s2_availability(sorted(E, key=lambda r: r["ts"]), _spec())
    assert v and all(x.invariant == "S2" for x in v)
    assert "floor" in v[0].message


def test_s2_503s_count_as_alive():
    E = _clean_timeline()
    for i in range(8):  # pure backpressure: degraded but ALIVE
        E.append({"ts": 40.0 + i, "kind": "request", "source": "loadgen",
                  "status": "busy", "replica": "replica0", "code": 503})
    assert check_s2_availability(sorted(E, key=lambda r: r["ts"]),
                                 _spec()) == []


def test_s2_fires_on_no_requests_at_all():
    E = [e for e in _clean_timeline() if e["kind"] != "request"]
    assert any("never ran" in v.message
               for v in check_s2_availability(E, _spec()))


def test_s3_fires_on_missed_adoption():
    E = _clean_timeline()
    # a good publish (epoch 2) nobody ever swaps to
    E.append({"ts": 25.0, "kind": "publish", "source": "trainer.h0",
              "epoch": 2, "path": "c2", "digest": "D2", "world_size": 1})
    v = check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 2  # one per replica
    assert all(x.invariant == "S3" and "never adopted" in x.message
               for x in v)


def test_s3_fires_on_late_adoption_but_not_after_replica_restart():
    E = _clean_timeline()
    E.append({"ts": 25.0, "kind": "publish", "source": "trainer.h0",
              "epoch": 2, "path": "c2", "digest": "D2", "world_size": 1})
    for r in ("replica0", "replica1"):  # adopted 30s late (deadline 20s)
        E.append({"ts": 55.0, "kind": "swap", "source": r, "epoch": 2,
                  "digest": "D2"})
    v = check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 2 and all("past deadline" in x.message for x in v)
    # ...but a replica that RESTARTED at ts=50 gets its deadline re-based
    # (a deliberate drain/relaunch must not be an instant red)
    E.append({"ts": 50.0, "kind": "serve_ready", "source": "replica0",
              "port": 1, "epoch": 2})
    v = check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 1 and "replica1" in v[0].message


def test_s3_fires_on_no_good_publish():
    E = [e for e in _clean_timeline()
         if e["kind"] not in ("publish", "verify_ok", "swap")]
    assert any("never published" in v.message
               for v in check_s3_adoption(E, _spec()))


def test_restarts_log_gen_world_fields(tmp_path):
    good = tmp_path / "restarts.log"
    good.write_text(  # host= is a hostname, not necessarily numeric
        "2026-08-05T10:00:00+00:00 host=tpu-vm-3 proc=1 rc=11 backoff=1s "
        "attempt=2/8 gen=3 world=0,1 action=restart\n"
        "2026-08-05T10:05:00+00:00 host=tpu-vm-3 proc=1 rc=0 backoff=0s "
        "attempt=2/8 gen=3 world=0,1 action=exit\n")
    assert check_restarts_log(str(good)) == []
    bad = tmp_path / "bad.log"
    bad.write_text(  # the elastic bookkeeping fields went missing
        "2026-08-05T10:00:00+00:00 host=1 proc=4242 rc=11 backoff=1s "
        "attempt=2/8 action=restart\n")
    v = check_restarts_log(str(bad))
    assert len(v) == 1 and v[0].invariant == "S3"
    assert "gen=" in v[0].message


def test_s4_fires_on_missing_or_red_lint():
    E = [e for e in _clean_timeline() if e["kind"] != "lint"]
    assert any("no lint event" in v.message for v in check_s4_analyzer(E))
    E.append({"ts": 30.0, "kind": "lint", "source": "supervisor", "rc": 1})
    assert any("rc=1" in v.message for v in check_s4_analyzer(E))


def _fleet_spec():
    return load_spec(
        '{"serve": {"replicas": 2, "max_replicas": 3, '
        '"scale_out_deadline_s": 30.0}, '
        '"availability": {"floor": 0.5, "window_s": 10.0, "min_samples": 3},'
        ' "adopt_deadline_s": 20}')


def test_s5_passes_on_serialized_wave_and_on_no_fleet_events():
    assert check_s5_fleet(_clean_timeline(), _spec()) == []  # vacuous
    E = _clean_timeline()
    E += [{"ts": 40.0, "kind": "drain_token_acquire", "source": "replica0",
           "replica": 0, "digest": "D0"},
          {"ts": 41.0, "kind": "drain_token_release", "source": "replica0",
           "replica": 0, "digest": "D0", "generation": 0},
          {"ts": 42.0, "kind": "drain_token_acquire", "source": "replica1",
           "replica": 1, "digest": "D0"},
          {"ts": 43.0, "kind": "drain_token_release", "source": "replica1",
           "replica": 1, "digest": "D0", "generation": 0}]
    assert check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec()) == []


def test_s5_fires_on_overlapping_drains():
    E = _clean_timeline()
    E += [{"ts": 40.0, "kind": "drain_token_acquire", "source": "replica0",
           "replica": 0, "digest": "D0"},
          {"ts": 41.0, "kind": "drain_token_acquire", "source": "replica1",
           "replica": 1, "digest": "D0"}]
    v = check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 1 and v[0].invariant == "S5"
    assert "two replicas draining at once" in v[0].message


def test_s5_takeover_closes_the_wedged_holders_interval():
    E = _clean_timeline()
    # replica0 acquires then dies without releasing; replica1's TTL
    # takeover force-closes the interval, so its acquire is NOT an overlap
    E += [{"ts": 40.0, "kind": "drain_token_acquire", "source": "replica0",
           "replica": 0, "digest": "D0"},
          {"ts": 50.0, "kind": "drain_token_takeover", "source": "replica1",
           "replica": 1, "digest": "D0"},
          {"ts": 50.1, "kind": "drain_token_acquire", "source": "replica1",
           "replica": 1, "digest": "D0"},
          {"ts": 51.0, "kind": "drain_token_release", "source": "replica1",
           "replica": 1, "digest": "D0", "generation": 0}]
    assert check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec()) == []


def test_s5_fires_on_survivor_digest_divergence():
    E = _clean_timeline()
    E.append({"ts": 25.0, "kind": "swap", "source": "replica1", "epoch": 0,
              "digest": "DX"})
    v = check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec())
    assert any("did not converge" in x.message for x in v)
    # ...unless that replica was retired by scale-in: survivors only
    E.append({"ts": 26.0, "kind": "replica_retire", "source": "supervisor",
              "replica": "replica1"})
    assert check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec()) == []
    assert replica_retire_times(E) == {"replica1": 26.0}


def test_s5_fires_on_convergence_to_a_stale_digest():
    E = _clean_timeline()
    for r in ("replica0", "replica1"):  # both end on a digest that is not
        E.append({"ts": 25.0, "kind": "swap", "source": r, "epoch": 0,
                  "digest": "STALE"})  # the newest good publish (D0)
    v = check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 1 and "newest good publish" in v[0].message


def test_s5_spike_load_demands_scale_out_within_deadline():
    E = _clean_timeline()
    E.append({"ts": 40.0, "kind": "spike_load", "source": "supervisor",
              "rps": 10.0})
    # scaler disarmed (max_replicas == 0): no demand on the timeline
    assert not any("spike_load" in x.message
                   for x in check_s5_fleet(E, _spec()))
    # armed spec: the unanswered spike is a violation...
    v = check_s5_fleet(sorted(E, key=lambda r: r["ts"]), _fleet_spec())
    assert any("never answered by a" in x.message for x in v)
    # ...a scale_out past the deadline still is...
    late = E + [{"ts": 75.0, "kind": "scale_out", "source": "supervisor",
                 "replica": "replica2", "replicas": 3}]
    v = check_s5_fleet(sorted(late, key=lambda r: r["ts"]), _fleet_spec())
    assert any("never answered by a" in x.message for x in v)
    # ...and one inside it settles the demand
    ok = E + [{"ts": 55.0, "kind": "scale_out", "source": "supervisor",
               "replica": "replica2", "replicas": 3}]
    assert check_s5_fleet(sorted(ok, key=lambda r: r["ts"]),
                          _fleet_spec()) == []


def test_s5_spike_with_fleet_already_at_max_is_excused():
    """Fuzzer-found checker bug: a spike landing when earlier scale_outs
    already grew the fleet to max_replicas demands nothing — the
    autoscaler has no headroom left (regression corpus:
    tests/data/scenarios/spike-at-max-fleet)."""
    E = _clean_timeline()
    E += [{"ts": 30.0, "kind": "scale_out", "source": "supervisor",
           "replica": "replica2", "replicas": 3},
          {"ts": 40.0, "kind": "spike_load", "source": "supervisor",
           "rps": 10.0}]
    assert check_s5_fleet(sorted(E, key=lambda r: r["ts"]),
                          _fleet_spec()) == []
    # a scale_in before the spike reopens headroom: demand is back on
    down = E + [{"ts": 35.0, "kind": "scale_in", "source": "supervisor",
                 "replica": "replica2", "replicas": 2}]
    v = check_s5_fleet(sorted(down, key=lambda r: r["ts"]), _fleet_spec())
    assert any("never answered by a" in x.message for x in v)


def test_s3_scale_in_retirement_excuses_adoption():
    E = _clean_timeline()
    E.append({"ts": 25.0, "kind": "publish", "source": "trainer.h0",
              "epoch": 2, "path": "c2", "digest": "D2", "world_size": 1})
    E.append({"ts": 26.0, "kind": "swap", "source": "replica0", "epoch": 2,
              "digest": "D2"})
    # without the retirement record, replica1 is a plain S3 red
    v = check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 1 and "replica1" in v[0].message
    # retired before its deadline and never came back: excused
    E.append({"ts": 30.0, "kind": "replica_retire", "source": "supervisor",
              "replica": "replica1"})
    assert check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec()) == []
    # a serve_ready AFTER the retirement voids the excusal (it rejoined)
    E.append({"ts": 35.0, "kind": "serve_ready", "source": "replica1",
              "port": 1, "epoch": 2})
    v = check_s3_adoption(sorted(E, key=lambda r: r["ts"]), _spec())
    assert len(v) == 1 and "replica1" in v[0].message


def test_cli_scenario_check_only_red_and_green(tmp_path, capsys):
    from ddp_classification_pytorch_tpu.cli.scenario import main

    ev_path = tmp_path / "events.jsonl"
    with open(ev_path, "w") as f:
        for r in _clean_timeline():
            f.write(json.dumps(r) + "\n")
    spec = ('{"availability": {"floor": 0.5, "window_s": 10.0, '
            '"min_samples": 3}, "adopt_deadline_s": 20}')
    main(["--scenario_spec", spec, "--check_only", "--events", str(ev_path),
          "--out", str(tmp_path)])
    assert "GREEN" in capsys.readouterr().out

    with open(ev_path, "a") as f:  # one stale-digest answer → rc 1
        f.write(json.dumps({"ts": 25.0, "kind": "request",
                            "source": "loadgen", "status": "ok",
                            "replica": "replica0", "digest": "BAD"}) + "\n")
    with pytest.raises(SystemExit) as exc:
        main(["--scenario_spec", spec, "--check_only",
              "--events", str(ev_path), "--out", str(tmp_path)])
    assert exc.value.code == 1
    assert "VIOLATION [S1]" in capsys.readouterr().err


def test_cli_scenario_check_only_rejects_malformed_events(tmp_path, capsys):
    """--check_only is strict: an unknown event kind or a kind missing a
    schema-required field is rc 2 (bad input), never a silent skip that
    would let a truncated/corrupt events.jsonl replay 'green'."""
    from ddp_classification_pytorch_tpu.cli.scenario import main

    spec = ('{"availability": {"floor": 0.5, "window_s": 10.0, '
            '"min_samples": 3}, "adopt_deadline_s": 20}')

    def run(extra):
        ev_path = tmp_path / "events.jsonl"
        with open(ev_path, "w") as f:
            for r in _clean_timeline() + extra:
                f.write(json.dumps(r) + "\n")
        main(["--scenario_spec", spec, "--check_only",
              "--events", str(ev_path), "--out", str(tmp_path)])

    with pytest.raises(SystemExit) as exc:  # unknown kind
        run([{"ts": 25.0, "kind": "warp_core_breach", "source": "x"}])
    assert exc.value.code == 2
    assert "unknown kind" in capsys.readouterr().err

    with pytest.raises(SystemExit) as exc:  # publish missing its digest
        run([{"ts": 25.0, "kind": "publish", "source": "trainer.h0",
              "epoch": 3, "path": "c3"}])
    assert exc.value.code == 2
    assert "missing required field" in capsys.readouterr().err


def test_validate_events_unit():
    from ddp_classification_pytorch_tpu.obs.events import (EVENT_SCHEMA,
                                                           validate_events)

    assert validate_events(_clean_timeline()) == []
    errs = validate_events([{"ts": 1.0, "kind": "nope", "source": "x"},
                            {"kind": "swap", "epoch": 0, "digest": "D"}])
    assert len(errs) == 2
    assert "unknown kind" in errs[0]
    assert "missing required field" in errs[1] and "ts" in errs[1]
    assert "scenario_start" in EVENT_SCHEMA and "request" in EVENT_SCHEMA


# ------------------------------------------------------- the full drill --


@pytest.mark.slow
def test_full_scenario_drill(tmp_path):
    """chaos_drill.sh phase 8: the complete supervised train→serve drill —
    elastic 2-host pod through NaN burst / torn ckpt / host SIGKILL /
    corrupt published candidate / watcher flake / reload-during-drain,
    2 replicas under offered load, S1–S4 asserted from events.jsonl."""
    env = dict(os.environ)
    env["CHAOS_PHASES"] = "8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "chaos_drill.sh"),
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, \
        f"drill failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}"
    assert "phase 8 OK" in proc.stdout
