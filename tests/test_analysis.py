"""Program-invariant analyzer (analysis/, cli.analyze).

Two halves, per the acceptance contract:

1. **Every detector must trip on a known-bad sample** — an undonated dead
   arg, a host callback inside jit, a uint8 input bypassing the normalize
   epilogue, a collective in a host-local program, host-sync idioms in a
   step factory, an uncatalogued CLI exit code, a steady-state recompile.
   The fixtures are 3-line jits/sources, so each proof costs milliseconds.

2. **The real repo passes** — ONE module-scoped run of the full registry
   audit (the only expensive trace/compile in this file; tier-1 budget),
   asserted clean, with the train steps' donation coverage at exactly 1.0
   (the before/after aliased-bytes evidence the MFU item owes).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.analysis import Finding
from ddp_classification_pytorch_tpu.analysis.compile_sentinel import (
    CompileSentinel,
    SteadyStateRecompile,
)
from ddp_classification_pytorch_tpu.analysis.jaxpr_audit import (
    AuditContext,
    StepSpec,
    audit_donation,
    audit_entry,
    audit_registry,
    build_registry,
    donation_evidence,
)
from ddp_classification_pytorch_tpu.analysis import baseline as baselib
from ddp_classification_pytorch_tpu.analysis.lint import (
    lint_factory_source,
    lint_rc_sites,
    lint_rc_source,
    lint_step_factories,
)
from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
    EVAL_COMMS,
    TRAIN_COMMS,
    _param_bytes,
    _spans_data,
    audit_collectives,
    audit_sharded_case,
    audit_sharding_table,
    collective_inventory,
    parse_replica_groups,
    sharded_registry,
    step_comms_evidence,
)

# --------------------------------------------------------------- fixtures --


@pytest.fixture(scope="module")
def audit():
    """The one expensive piece: the full registry audit (state inits, the
    jaxpr traces incl. the dp×tp entries, two donated-step compiles) —
    shared by every real-repo assertion below."""
    from types import SimpleNamespace

    ctx = AuditContext()
    findings, specs = audit_registry(ctx)
    return SimpleNamespace(ctx=ctx, findings=findings,
                           specs={s.name: s for s in specs})


@pytest.fixture(scope="module")
def sharded(audit):
    """Tier-1-lean sharded matrix subset: ONE lower+compile per composed
    mesh — the dp2 train cell (the acceptance cell: gradient all-reduce
    set + donation coverage under a ≥2-device mesh) and the dp2tp2 eval
    cell (the model-axis layout). The full 8-cell matrix runs in the
    slow-marked CLI test and in scripts/lint.sh."""
    from types import SimpleNamespace

    want = {"train_step@dp2", "eval_step@dp2tp2"}
    findings, records = [], {}
    for case in sharded_registry():
        if case.key not in want:
            continue
        f, rec = audit_sharded_case(case, audit.ctx)
        findings += f
        records[case.key] = rec
    assert set(records) == want  # the registry must keep both cells
    return SimpleNamespace(findings=findings, records=records)


def _fixture_spec(fn, args, **kw):
    return StepSpec(name="fixture", factory="tests:fixture",
                    build=lambda ctx: (fn, args), **kw)


# ------------------------------------------------- detectors must trip --


def test_donation_detector_fires_on_unaliased_donated_arg(audit):
    """A donated buffer with no same-shape output cannot alias — the audit
    must report the gap with byte counts, not stay silent."""
    fn = jax.jit(lambda s: s[:2].sum(), donate_argnums=0)
    findings, ev = audit_donation(fn, (jnp.zeros((8, 8), jnp.float32),),
                                  "fixture")
    assert findings and findings[0].check == "donation"
    assert ev["donated_bytes"] == 8 * 8 * 4
    assert ev["aliased_bytes"] < ev["donated_bytes"]
    assert "bytes" in findings[0].message


def test_donation_detector_fires_on_missing_donation(audit):
    """A registry entry that PROMISES donation must fail when the factory
    jits without donate_argnums (the exact regression the ROADMAP's MFU
    item guards against)."""
    fn = jax.jit(lambda s, x: (s + x.sum(), x * 2))  # state NOT donated
    spec = _fixture_spec(fn, (jnp.zeros((16, 16), jnp.float32),
                              jax.ShapeDtypeStruct((4,), jnp.float32)),
                         donate=(0,))
    findings = audit_entry(spec, audit.ctx)
    assert any(f.check == "donation" for f in findings)


def test_callback_detector_fires_on_debug_print(audit):
    def bad(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2

    spec = _fixture_spec(jax.jit(bad),
                         (jax.ShapeDtypeStruct((4,), jnp.float32),),
                         no_donate_reason="fixture")
    findings = audit_entry(spec, audit.ctx)
    assert any(f.check == "callback" for f in findings)
    assert any("debug_print" in str(f.evidence) for f in findings)


def test_collective_detector_fires_and_allowlist_clears(audit):
    from jax.sharding import PartitionSpec as P

    from ddp_classification_pytorch_tpu.utils.compat import shard_map_unchecked

    mesh = audit.ctx.mesh
    fn = jax.jit(shard_map_unchecked(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P()))
    args = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    hit = audit_entry(_fixture_spec(fn, args, no_donate_reason="fixture"),
                      audit.ctx)
    assert any(f.check == "collectives" and "psum" in f.message for f in hit)
    clean = audit_entry(_fixture_spec(fn, args, no_donate_reason="fixture",
                                      allow_collectives=True), audit.ctx)
    assert not [f for f in clean if f.check == "collectives"]


def test_uint8_detector_fires_on_epilogue_bypass(audit):
    """Raw pixels converted to float WITHOUT the /255 normalize = the uint8
    dataplane contract broken (PR 3's NOTE: every new step must call
    device_input_epilogue)."""
    fn = jax.jit(lambda x: x.astype(jnp.float32).sum())
    spec = _fixture_spec(fn, (jax.ShapeDtypeStruct((4, 8, 8, 3), jnp.uint8),),
                         no_donate_reason="fixture", uint8_input=True)
    findings = audit_entry(spec, audit.ctx)
    assert any(f.check == "uint8-epilogue" for f in findings)


def test_uint8_detector_fires_on_direct_consumption(audit):
    """uint8 fed straight into arithmetic (no convert at all) must flag."""
    fn = jax.jit(lambda x: (x * 2).sum())
    spec = _fixture_spec(fn, (jax.ShapeDtypeStruct((4, 8, 8, 3), jnp.uint8),),
                         no_donate_reason="fixture", uint8_input=True)
    findings = audit_entry(spec, audit.ctx)
    assert any(f.check == "uint8-epilogue" for f in findings)


def test_uint8_detector_passes_the_real_epilogue(audit):
    from ddp_classification_pytorch_tpu.train.steps import device_input_epilogue

    fn = jax.jit(lambda x: device_input_epilogue(x).sum())
    spec = _fixture_spec(fn, (jax.ShapeDtypeStruct((4, 8, 8, 3), jnp.uint8),),
                         no_donate_reason="fixture", uint8_input=True)
    findings = audit_entry(spec, audit.ctx)
    assert not [f for f in findings if f.check == "uint8-epilogue"]


_BAD_FACTORY = '''
import time
import numpy as np

def make_bad_step(model):
    def step(state, images):
        t0 = time.time()
        print("loss so far")
        host = np.asarray(images)
        return float(state.loss) + state.loss.item() + host.mean() + t0
    return step
'''


def test_host_sync_lint_fires_on_every_idiom():
    findings = lint_factory_source(_BAD_FACTORY, function="make_bad_step")
    msgs = " | ".join(f.message for f in findings)
    for idiom in (".item()", "print", "np.asarray", "time.time", "float()"):
        assert idiom in msgs, (idiom, msgs)
    assert len(findings) == 5


def test_host_sync_lint_flags_stale_provenance():
    findings = lint_factory_source("x = 1\n", function="make_missing")
    assert findings and "not found" in findings[0].message


def test_rc_lint_fires_on_uncatalogued_codes():
    assert lint_rc_source("import sys\nsys.exit(13)\n")
    assert lint_rc_source("raise SystemExit(99)\n")
    assert lint_rc_source("import sys\nsys.exit(compute_rc())\n")


def test_rc_lint_passes_catalogued_patterns():
    src = (
        "import sys\n"
        "sys.exit(2)\n"
        "raise SystemExit(0 if ok else 1)\n"
        "raise SystemExit(SentinelDiverged.exit_code)\n"
        "raise SystemExit(e.code)\n"
    )
    assert lint_rc_source(src) == []


# --------------------------------------------------- the real repo passes --


def test_registry_names_every_step_program():
    names = {s.name for s in build_registry()}
    assert names == {"train_step", "eval_step", "nested_eval_step",
                     "plc_predict", "topk_predict", "train_step_survivor",
                     # the bf16-wire gradient-reduction variant: a shard_map
                     # section of the train step (--grad_reduce_dtype bfloat16)
                     "train_step_bf16_reduce",
                     # the same eval-family programs traced under the
                     # composed dp×tp mesh (sharded audit satellites)
                     "eval_step_dp_tp", "nested_eval_step_dp_tp",
                     "plc_predict_dp_tp", "topk_predict_dp_tp",
                     # the dp-sharded serving predict (serve mesh assembles
                     # data-sharded global batches; docs/serving.md)
                     "topk_predict_serve_dp", "topk_predict_serve_dp_tp",
                     # the fleet-width serve predict (dp4 — the autoscaler's
                     # max-replica provisioning shape; docs/serving.md)
                     "topk_predict_serve_fleet",
                     # the K-microbatch accumulated step (--grad_accum 4):
                     # lax.scan over microbatches, ONE deferred data-axis
                     # gradient reduction per optimizer step
                     "train_step_accum4"}
    for spec in build_registry():
        # every entry either donates or documents why it must not
        assert spec.donate or spec.no_donate_reason, spec.name


def test_self_audit_repo_is_clean(audit):
    assert audit.findings == [], [str(f) for f in audit.findings]


def test_train_steps_donation_fully_aliased(audit):
    """The MFU item's donation audit: every donated state byte is aliased
    in the train step's executable — no buffer round-trips HBM."""
    don = audit.specs["train_step"].evidence["donation"]
    assert don["donated_bytes"] > 10_000_000, don  # real state
    assert don["donation_coverage"] == 1.0, don
    assert don["unaliased"] == [], don


def test_step_factories_lint_clean():
    assert lint_step_factories() == []


def test_cli_rc_sites_lint_clean():
    assert lint_rc_sites() == []


def test_analyze_cli_rc2_on_bad_pass():
    from ddp_classification_pytorch_tpu.cli.analyze import main

    with pytest.raises(SystemExit) as e:
        main(["--passes", "bogus"])
    assert e.value.code == 2


def test_analyze_cli_rc1_on_findings(tmp_path):
    """Findings → rc 1, proven via an explicit rc-lint target (the same
    surface the CLI uses for the cli/ package)."""
    from ddp_classification_pytorch_tpu.cli.analyze import main

    bad = tmp_path / "bad_cli.py"
    bad.write_text("import sys\nsys.exit(13)\n")
    with pytest.raises(SystemExit) as e:
        main(["--passes", "lint", "--rc-paths", str(bad)])
    assert e.value.code == 1


def test_analyze_cli_lint_pass_clean(capsys):
    from ddp_classification_pytorch_tpu.cli.analyze import main

    main(["--passes", "lint"])  # returns (rc 0) or raises SystemExit
    assert "clean" in capsys.readouterr().out


# ------------------------------------------------------- compile sentinel --


def test_compile_sentinel_counts_compiles_not_cache_hits():
    sent = CompileSentinel(tag="t").arm()
    try:
        @jax.jit
        def fresh_fn(x):
            return x * 3 + 1

        fresh_fn(np.ones(3, np.float32))
        assert any(e.name == "fresh_fn" for e in sent.take())
        fresh_fn(np.ones(3, np.float32))  # cache hit: silent
        assert [e for e in sent.take() if e.name == "fresh_fn"] == []
        fresh_fn(np.ones(5, np.float32))  # new shape: recompile
        with pytest.raises(SteadyStateRecompile):
            sent.check(strict=True)
        assert sent.violations >= 1
    finally:
        sent.disarm()
    assert not sent.armed


def test_compile_sentinel_event_carries_signature():
    sent = CompileSentinel(tag="t").arm()
    try:
        @jax.jit
        def sig_fn(x):
            return x + 1

        sig_fn(np.ones((2, 7), np.float32))
        events = [e for e in sent.take() if e.name == "sig_fn"]
        assert events and "2,7" in events[0].signature
    finally:
        sent.disarm()


def _fake_predict():
    """A tiny jitted predict with the serve signature — the engine's
    compile accounting doesn't care that it isn't a model."""

    @jax.jit
    def step(state, images):
        x = images.astype(jnp.float32).mean(axis=(1, 2, 3)) * state["w"]
        scores = jnp.stack([x, -x], axis=1)
        idx = jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32), scores.shape)
        return scores, idx

    return step


def _engine(predict, **kw):
    from ddp_classification_pytorch_tpu.serve.engine import ServingEngine

    kw.setdefault("image_size", 8)
    kw.setdefault("input_dtype", "uint8")
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_timeout_ms", 5.0)
    kw.setdefault("buckets", (2, 4))
    return ServingEngine({"w": jnp.ones(())}, predict, **kw)


def test_serve_warmup_asserts_exact_compile_count_and_stays_armed():
    predict = _fake_predict()
    engine = _engine(predict)
    try:
        engine.warmup()  # cold predict: exactly len(buckets) programs
        assert engine.compiled_programs() == 2
        assert engine.compile_sentinel is not None
        assert engine.compile_sentinel.armed
        # a second engine over the now-warm predict must not false-positive
        engine2 = _engine(predict)
        engine2.warmup()
        engine2.close()
    finally:
        engine.close()


def test_serve_steady_state_recompile_counted_and_strict_fatal():
    from ddp_classification_pytorch_tpu.serve.metrics import ServeMetrics

    predict = _fake_predict()
    metrics = ServeMetrics()
    engine = _engine(predict, metrics=metrics, strict_compile=True)
    try:
        engine.warmup()
        # steady state: a bucket-shaped batch is a cache hit, no violation
        engine.submit(np.zeros((8, 8, 3), np.uint8))
        assert engine.process_once() == 1
        assert metrics.snapshot()["recompiles"] == 0
        # someone sneaks a non-bucket shape through the shared predict:
        # the NEXT batch boundary must catch the compile
        predict({"w": jnp.ones(())}, np.zeros((3, 8, 8, 3), np.uint8))
        engine.submit(np.zeros((8, 8, 3), np.uint8))
        with pytest.raises(SteadyStateRecompile):
            engine.process_once()
        assert engine.fatal_error is not None
        assert metrics.snapshot()["recompiles"] >= 1
        assert engine.closed  # intake stopped
    finally:
        engine.close()


def test_donation_evidence_fields():
    """`audit_donation` rides this helper: the fields must exist and a
    fully-aliasable donated arg must report coverage 1.0."""
    fn = jax.jit(lambda s, x: (s + x.sum(), x * 2), donate_argnums=0)
    ev = donation_evidence(fn, (jnp.zeros((32, 32), jnp.float32),
                                jax.ShapeDtypeStruct((4,), jnp.float32)))
    assert ev["donated_bytes"] == 32 * 32 * 4
    assert ev["donation_coverage"] == 1.0
    assert ev["unaliased"] == []
    assert isinstance(ev["temp_bytes"], int)


def test_finding_renders_as_one_line():
    f = Finding("donation", "train_step", "gap", {"bytes": 4})
    assert str(f) == "[donation] train_step: gap"


# -------------------------------------------- sharding & comms audit --


def test_sharded_cells_audit_clean(sharded):
    assert sharded.findings == [], [str(f) for f in sharded.findings]


def test_dp_train_step_carries_gradient_allreduce_set(sharded, audit):
    """The acceptance invariant: under a ≥2-device data mesh the ZeRO-1
    train step carries exactly the gradient all-reduce plus the param
    all-gather that re-assembles the shard-local optimizer update (no
    stray kinds), the data-spanning reduce payload covers every parameter
    byte (the gradient set is present, not truncated), and donation
    coverage stays exactly 1.0."""
    rec = sharded.records["train_step@dp2"]
    assert set(rec["collectives"]) == {"all-reduce", "all-gather"}
    ar = rec["collectives"]["all-reduce"]
    got = sum(b for label, b in ar["axes"].items() if _spans_data(label))
    assert got >= _param_bytes(audit.ctx) > 10_000_000
    # the ZeRO param gather is weight-sized, not a stray control gather
    assert rec["collectives"]["all-gather"]["bytes"] > 10_000_000
    assert rec["donation_coverage"] == 1.0


def test_eval_dp_tp_cell_is_collective_lean_and_model_sharded(sharded):
    """Under the composed dp×tp mesh eval stays control-sized on the wire
    (scalar metric reductions only) and GSPMD actually split the fc kernel
    over the model axis while the batch rode the data axis."""
    rec = sharded.records["eval_step@dp2tp2"]
    assert rec["collective_bytes_per_step"] < 16 * 1024
    specs = " | ".join(rec["sharded_leaves"].values())
    assert "'model'" in specs and "'data'" in specs


def test_sharded_records_match_committed_baseline(sharded):
    """The tier-1 fence: the lean cells, recompiled here, must sit within
    the committed baseline's tolerances (subset mode: the full matrix is
    lint.sh's job)."""
    base = baselib.load_baseline()
    diff = baselib.diff_baseline(sharded.records, base, subset=True)
    assert diff == [], [str(f) for f in diff]


def test_zero_detector_fires_on_replicated_buffer(audit):
    """A weight-sized buffer replicated across a >1 data axis must flag —
    and the same buffer sharded over data (or a 1-wide data mesh) must
    not."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = audit.ctx.composed_mesh("dp2")
    rows = [{"path": ".params.big", "shape": (2048, 2048),
             "dtype": "float32", "bytes": 2048 * 2048 * 4,
             "spec": str(P()), "_sharding": NamedSharding(mesh, P())}]
    findings = audit_sharding_table(rows, mesh, "fixture")
    assert findings and findings[0].check == "sharding"
    assert "replicated" in findings[0].message
    rows[0]["_sharding"] = NamedSharding(mesh, P("data"))
    assert audit_sharding_table(rows, mesh, "fixture") == []
    assert audit_sharding_table(rows, audit.ctx.mesh, "fixture") == []


def test_resharding_detector_fires_on_forced_gather(audit):
    """A data-sharded weight-sized array forced replicated mid-program
    compiles to a big all-gather: the implicit-resharding detector and the
    per-op payload cap must both trip."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = audit.ctx.composed_mesh("dp2")
    x = jax.ShapeDtypeStruct(
        (1024, 256), jnp.float32,
        sharding=NamedSharding(mesh, P("data")))

    @jax.jit
    def gathered(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())) * 2.0

    ev = step_comms_evidence(gathered, (x,), donated_argnums=(), mesh=mesh)
    findings = audit_collectives(ev["collectives"], EVAL_COMMS, "fixture")
    assert any(f.check == "resharding" for f in findings), \
        [str(f) for f in findings]
    assert any(f.check == "comms" for f in findings)


def test_comms_detector_fires_on_policy_violations():
    """Disallowed kind + oversized allowed kind, on a fabricated inventory
    (detector logic is pure — no compile needed)."""
    inv = {"kinds": {"all-reduce": {"count": 1, "bytes": 262144,
                                    "max_op_bytes": 262144,
                                    "axes": {"data": 262144}},
                     "all-to-all": {"count": 1, "bytes": 64,
                                    "max_op_bytes": 64,
                                    "axes": {"data": 64}}},
           "total_bytes": 262208}
    findings = audit_collectives(inv, EVAL_COMMS, "fixture")
    msgs = " | ".join(f.message for f in findings)
    assert "all-to-all" in msgs  # kind outside the policy
    assert "262,144" in msgs     # allowed kind over the per-op cap
    assert all(f.check == "comms" for f in findings)


def test_grad_allreduce_floor_detector():
    """The missing-gradient-set detector: no all-reduce at all fires;
    model-axis-only reduces do NOT satisfy the data-spanning floor;
    full-mesh ('all', XLA's replica_groups={} form) reduces do."""
    empty = {"kinds": {}, "total_bytes": 0}
    findings = audit_collectives(empty, TRAIN_COMMS, "fixture",
                                 min_grad_bytes=1000)
    assert findings and "gradient all-reduce set" in findings[0].message
    inv = {"kinds": {"all-reduce": {"count": 1, "bytes": 2000,
                                    "max_op_bytes": 2000,
                                    "axes": {"model": 2000}}},
           "total_bytes": 2000}
    assert audit_collectives(inv, TRAIN_COMMS, "fixture",
                             min_grad_bytes=1000)
    inv["kinds"]["all-reduce"]["axes"] = {"all": 2000}
    assert audit_collectives(inv, TRAIN_COMMS, "fixture",
                             min_grad_bytes=1000) == []


def test_parse_replica_groups_forms():
    assert parse_replica_groups("replica_groups={{0,2},{1,3}}") == frozenset(
        {frozenset({0, 2}), frozenset({1, 3})})
    assert parse_replica_groups("replica_groups=[2,2]<=[4]") == frozenset(
        {frozenset({0, 1}), frozenset({2, 3})})
    assert parse_replica_groups(
        "replica_groups=[2,2]<=[2,2]T(1,0)") == frozenset(
        {frozenset({0, 2}), frozenset({1, 3})})
    assert parse_replica_groups("replica_groups={}") == frozenset()
    assert parse_replica_groups("no groups here") is None


def test_empty_replica_groups_attributes_to_full_mesh(audit):
    """HLO `replica_groups={}` = every device, one group — the form XLA
    emits for the dp×tp full-mesh gradient reduces. It must land on 'all'
    (which spans the data axis), never on degenerate 'none' — the exact
    misattribution that would false-fire the gradient floor."""
    mesh = audit.ctx.composed_mesh("dp2tp2")
    hlo = ("  %r = f32[100]{0} all-reduce(f32[100]{0} %x), "
           "replica_groups={}, to_apply=%sum\n")
    inv = collective_inventory(hlo, mesh)
    assert inv["kinds"]["all-reduce"]["axes"] == {"all": 400}
    assert _spans_data("all") and _spans_data("data+model")
    assert not _spans_data("model")


def test_step_comms_evidence_fields(audit):
    """One program's evidence outside the matrix: donation fields plus the
    comms/memory fields, all from ONE compile."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = audit.ctx.composed_mesh("dp2")
    s = jnp.zeros((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    fn = jax.jit(lambda s, x: (s + x.sum(), x * 2), donate_argnums=0)
    ev = step_comms_evidence(fn, (s, x), mesh=mesh)
    assert ev["donated_bytes"] == 64 * 64 * 4
    assert ev["donation_coverage"] == 1.0
    assert ev["collective_bytes_per_step"] > 0  # the sharded partial sum
    assert ev["peak_hbm_bytes"] > 0
    assert ev["memory"]["peak_hbm_bytes"] == ev["peak_hbm_bytes"]


# ------------------------------------------------------ program baselines --


def _baseline_rec(**over):
    rec = {"collectives": {"all-reduce": {"count": 2, "bytes": 1000,
                                          "max_op_bytes": 800,
                                          "axes": {"data": 1000}}},
           "collective_bytes_per_step": 1000,
           "peak_hbm_bytes": 10_000,
           "sharded_leaves": {
               ".params.fc.kernel": "PartitionSpec(None, 'model')"},
           "donation_coverage": 1.0}
    rec.update(over)
    return rec


def test_baseline_diff_flags_each_drift_class():
    base = {"tolerances": dict(baselib.DEFAULT_TOLERANCES),
            "programs": {"p@dp2": _baseline_rec()}}
    # within tolerance (and shrinkage) is NOT drift
    ok = {"p@dp2": _baseline_rec(collective_bytes_per_step=1050,
                                 peak_hbm_bytes=9_000)}
    assert baselib.diff_baseline(ok, base) == []
    drifted = {"p@dp2": _baseline_rec(
        collectives={"all-reduce": {"count": 2, "bytes": 1000,
                                    "max_op_bytes": 800,
                                    "axes": {"data": 1000}},
                     "all-gather": {"count": 1, "bytes": 200,
                                    "max_op_bytes": 200,
                                    "axes": {"model": 200}}},
        collective_bytes_per_step=1200,             # +20% payload
        peak_hbm_bytes=12_000,                      # +20% peak
        sharded_leaves={},                          # fc now replicated
        donation_coverage=0.9)}                     # regression
    findings = baselib.diff_baseline(drifted, base)
    joined = " | ".join(f.message for f in findings)
    assert "new collective kind" in joined
    assert "payload grew" in joined
    assert "peak HBM grew" in joined
    assert "downgrade" in joined
    assert "coverage regressed" in joined
    assert len(findings) == 5
    assert all(f.check == "baseline" for f in findings)


def test_baseline_diff_flags_missing_and_new_programs():
    base = {"programs": {"gone@dp2": _baseline_rec()}}
    findings = baselib.diff_baseline({"new@dp2": _baseline_rec()}, base)
    joined = " | ".join(f.message for f in findings)
    assert "not in the committed baseline" in joined
    assert "missing from the fresh audit" in joined
    # subset mode (the tier-1 lean cells): absent programs don't flag,
    # an unknown new one still does
    sub = baselib.diff_baseline({"new@dp2": _baseline_rec()}, base,
                                subset=True)
    assert len(sub) == 1 and "not in the committed baseline" in sub[0].message


def test_baseline_roundtrip_and_provenance(tmp_path):
    path = str(tmp_path / "b.json")
    records = {"p@dp2": _baseline_rec()}
    baselib.write_baseline(records, path, context={"arch": "resnet18"})
    base = baselib.load_baseline(path)
    assert base["programs"] == records
    assert base["_provenance"]["config"]["arch"] == "resnet18"
    # the tolerances block is the sharding defaults plus the dtype pass's
    # cast-churn band (one file fences both passes)
    from ddp_classification_pytorch_tpu.analysis.dtype_audit import (
        DTYPE_TOLERANCES,
    )

    assert base["tolerances"] == {**baselib.DEFAULT_TOLERANCES,
                                  **DTYPE_TOLERANCES}
    assert baselib.diff_baseline(records, base) == []
    with pytest.raises(FileNotFoundError, match="--update-baseline"):
        baselib.load_baseline(str(tmp_path / "absent.json"))


def test_analyze_parser_accepts_baseline_flags():
    from ddp_classification_pytorch_tpu.cli.analyze import build_parser

    ns = build_parser().parse_args(["--diff-baseline"])
    assert ns.diff_baseline and not ns.update_baseline
    ns = build_parser().parse_args(["--diff_baseline",
                                    "--baseline", "x.json"])
    assert ns.diff_baseline and ns.baseline == "x.json"
    assert build_parser().parse_args(["--update-baseline"]).update_baseline


@pytest.mark.slow
def test_analyze_cli_diff_baseline_clean(capsys):
    """The acceptance run: the FULL sharded matrix recompiled and diffed
    against the committed baseline exits 0 on a clean tree."""
    from ddp_classification_pytorch_tpu.cli.analyze import main

    main(["--passes", "sharding", "--diff-baseline"])
    assert "clean" in capsys.readouterr().out
