"""Dtype-flow auditor (analysis/dtype_audit.py) — the numerics contracts.

Same two halves as test_analysis.py, per the acceptance contract:

1. **Every D1–D6 detector must trip on a known-bad sample** — an f64 leak,
   a bf16 master-weight / optimizer hop, a bf16 dot without f32
   accumulation, a large bf16 reduction, a bf16 softmax, an undeclared
   bf16 collective, a no-op round-trip cast chain, an int→bf16 label
   downcast. Fixtures are 3-line traces, milliseconds each.

2. **The real repo passes** — a module-scoped audit of a lean cell subset
   (the f32 train step, the shipped-bf16 train/serve cells, the composed
   bf16-wire cell, the declared `--ln_bf16` cell), asserted clean AND
   matching the committed `dtype_programs` baseline; the full 19-cell
   matrix runs slow-marked and in scripts/lint.sh.

Plus the parity pins for the real findings this auditor caught and this
PR fixed (the f32→bf16→f32 pool/LN seams in resnet and vit): the fixed
seam must sit within 2e-4 of the all-f32 seam reference while the OLD
recipe must NOT — proving both the fix and that the pin bites.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.analysis import baseline as baselib
from ddp_classification_pytorch_tpu.analysis.dtype_audit import (
    REDUCE_ELEMS,
    WAIVER_BF16_REDUCE,
    WAIVER_BF16_SOFTMAX,
    WAIVER_BF16_TRUNK,
    WAIVER_BF16_WIRE,
    WAIVER_LN_BF16,
    WAIVER_REASONS,
    audit_dtype_registry,
    audit_program,
    diff_dtype_baseline,
    dtype_registry,
    step_dtype_evidence,
)
from ddp_classification_pytorch_tpu.analysis.jaxpr_audit import AuditContext
from ddp_classification_pytorch_tpu.analysis.lint import (
    lint_jit_sites,
    lint_jit_source,
)
from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
    audit_wire_dtypes,
    collective_wire_dtypes,
)
from ddp_classification_pytorch_tpu.utils.compat import shard_map_unchecked

# --------------------------------------------------------------- fixtures --

# the tier-1-lean cell subset: one f32 cell (D2 on the pinned audit
# config), the shipped bf16 compute cells (train + the serve softmax
# customer), the two-lever composition, and the declared --ln_bf16 cell
_LEAN_CELLS = {
    "train_step",
    "train_step#bf16",
    "topk_predict_serve#bf16",
    "train_step_bf16_reduce#bf16",
    "vit_eval#ln_bf16",
}


@pytest.fixture(scope="module")
def dtype_audit():
    """The one expensive piece in this file: two extra state inits (bf16
    resnet, bf16 vit) + jaxpr traces — no compiles. Shared by every
    real-repo assertion below."""
    from types import SimpleNamespace

    ctx = AuditContext()
    cases = [c for c in dtype_registry() if c.name in _LEAN_CELLS]
    findings, records = audit_dtype_registry(ctx, cases=cases)
    return SimpleNamespace(ctx=ctx, findings=findings, records=records)


# ------------------------------------------------- detectors must trip --


def test_d1_fires_on_f64_aval():
    """A NumPy f64 scalar leaking into a jit under x64 must be caught at
    the aval level, not discovered as a TPU-vs-CPU parity break."""
    with jax.enable_x64(True):
        findings, _ = audit_program(lambda x: x * 2.0,
                                    (np.zeros((4,), np.float64),))
    assert any(f.check == "dtype-f64" for f in findings)


def test_d2_fires_on_bf16_master_leaf():
    """A bf16 leaf under a params path breaks the master-weights invariant
    on BOTH sides of the step (input and output directions report)."""
    state = {"params": {"w": jnp.zeros((4,), jnp.bfloat16)}}
    findings, _ = audit_program(lambda s: s, (state,), train=True)
    dirs = {f.evidence["direction"] for f in findings
            if f.check == "dtype-master"}
    assert dirs == {"input", "output"}


def test_d2_fires_on_bf16_optimizer_update():
    """An optimizer update that dips through bf16 produces the opt_state
    output from a sub-f32 eqn — the classic silent-divergence regression."""
    state = {"opt_state": {"mu": jnp.zeros((4,), jnp.float32)}}

    def fn(s):
        mu = s["opt_state"]["mu"].astype(jnp.bfloat16) * 0.9
        return {"opt_state": {"mu": mu.astype(jnp.float32)}}

    findings, _ = audit_program(fn, (state,), train=True)
    assert any(f.check == "dtype-master" and "produced by" in f.message
               for f in findings)


def test_d2_clean_on_f32_update():
    state = {"opt_state": {"mu": jnp.zeros((4,), jnp.float32)},
             "params": {"w": jnp.zeros((4,), jnp.float32)}}
    findings, _ = audit_program(
        lambda s: jax.tree_util.tree_map(lambda x: x * 0.9, s),
        (state,), train=True)
    assert not findings


def test_d3_fires_on_bf16_dot_without_f32_accum():
    a = jnp.zeros((8, 8), jnp.bfloat16)
    findings, summary = audit_program(lambda a, b: a @ b, (a, a))
    assert any(f.check == "dtype-accum" for f in findings)
    assert summary["accum"]["dot_general"]["sub_f32"] == 1

    # the declared-trunk waiver admits it (and banks it in the summary)
    waived, _ = audit_program(lambda a, b: a @ b, (a, a),
                              waivers=frozenset({WAIVER_BF16_TRUNK}))
    assert not waived

    # preferred_element_type=f32 is clean WITHOUT any waiver
    f32acc, s2 = audit_program(
        lambda a, b: jax.lax.dot(a, b, preferred_element_type=jnp.float32),
        (a, a))
    assert not f32acc
    assert s2["accum"]["dot_general"]["f32_accum"] == 1


def test_d3_fires_on_large_bf16_reduction():
    # the raw reduce_sum primitive keeps the operand dtype (jnp.sum
    # upcasts f16/bf16 to f32 internally — which is WHY the repo audits
    # clean); code reaching for lax directly is what this detector guards
    def raw_sum(x):
        return jax.lax.reduce_sum_p.bind(x, axes=(0,))

    x = jnp.zeros((2 * REDUCE_ELEMS,), jnp.bfloat16)
    findings, summary = audit_program(raw_sum, (x,))
    assert any(f.check == "dtype-accum" and "folds" in f.message
               for f in findings)
    assert summary["large_reductions"]["sub_f32"] == 1

    # explicit f32 accumulation is clean; so is the declared waiver —
    # and ln_bf16 IMPLIES bf16_reduce (the LN-at-width story)
    assert not audit_program(lambda x: jnp.sum(x, dtype=jnp.float32), (x,))[0]
    for w in (WAIVER_BF16_REDUCE, WAIVER_LN_BF16):
        assert not audit_program(raw_sum, (x,), waivers=frozenset({w}))[0]


def test_d3_small_reduction_is_in_family():
    """A LayerNorm-sized fold (hidden dim ≪ REDUCE_ELEMS) is the recipe's
    accepted rounding, not a finding."""
    x = jnp.zeros((8, 192), jnp.bfloat16)
    findings, _ = audit_program(lambda x: jnp.sum(x, axis=-1), (x,))
    assert not findings


def test_d4_fires_on_bf16_softmax():
    x = jnp.zeros((4, 16), jnp.bfloat16)
    findings, summary = audit_program(jax.nn.softmax, (x,))
    assert any(f.check == "dtype-loss-head" for f in findings)
    assert summary["exp_log_sub_f32"] >= 1
    assert not audit_program(jax.nn.softmax, (x,),
                             waivers=frozenset({WAIVER_BF16_SOFTMAX}))[0]
    assert not audit_program(jax.nn.softmax,
                             (x.astype(jnp.float32),))[0]


def test_d5_fires_on_undeclared_bf16_collective():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("d",))
    P = jax.sharding.PartitionSpec
    fn = shard_map_unchecked(lambda x: jax.lax.psum(x, "d"),
                             mesh=mesh, in_specs=P("d"), out_specs=P())
    x = jnp.zeros((2, 4), jnp.bfloat16)
    findings, summary = audit_program(fn, (x,))
    assert any(f.check == "dtype-wire" for f in findings)
    assert summary["collective_dtypes"] == ["bfloat16"]
    assert not audit_program(fn, (x,),
                             waivers=frozenset({WAIVER_BF16_WIRE}))[0]


def test_d6_fires_on_roundtrip_cast_chain():
    x = jnp.zeros((4,), jnp.float32)
    findings, summary = audit_program(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0, (x,))
    assert any(f.check == "dtype-cast" and "round-trip" in f.message
               for f in findings)
    assert summary["cast_roundtrips"] == 1

    # compute between the casts makes it a REAL precision seam, not a
    # no-op — and that is the trunk's business, not D6's
    clean, _ = audit_program(
        lambda x: (x.astype(jnp.bfloat16) * 2).astype(jnp.float32), (x,))
    assert not [f for f in clean if f.check == "dtype-cast"]


def test_d6_fires_on_label_downcast():
    labels = jnp.zeros((8,), jnp.int32)
    findings, _ = audit_program(lambda i: i.astype(jnp.bfloat16), (labels,))
    assert any(f.check == "dtype-cast" and "label" in f.message
               for f in findings)


def test_unknown_waiver_token_is_an_error():
    with pytest.raises(ValueError, match="undeclared waiver"):
        audit_program(lambda x: x, (jnp.zeros(2),),
                      waivers=frozenset({"bogus_token"}))


def test_waiver_catalogue_is_documented():
    """Every waiver token must carry a reviewed reason AND appear in the
    docs' waiver table — an undocumented waiver cannot land silently."""
    docs = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "analysis.md")
    with open(docs) as f:
        text = f.read()
    for token, reason in WAIVER_REASONS.items():
        assert reason.strip(), token
        assert f"`{token}`" in text, (
            f"waiver `{token}` missing from docs/analysis.md")


# ----------------------------------------------------- baseline drift --


def _rec(**over):
    rec = {
        "n_eqns": 10,
        "casts": {"float32->bfloat16": 4, "bfloat16->float32": 4},
        "cast_roundtrips": 0,
        "bf16_op_fraction": 1.0,
        "accum": {"dot_general": {"sub_f32": 2, "f32_accum": 1, "f32": 0},
                  "conv": {"sub_f32": 3, "f32_accum": 0, "f32": 0}},
        "large_reductions": {"sub_f32": 0, "f32": 1},
        "exp_log_sub_f32": 0,
        "collective_dtypes": ["float32"],
        "waivers": [WAIVER_BF16_TRUNK],
    }
    rec.update(over)
    return rec


def _base():
    return {"dtype_programs": {"cell": _rec()}, "tolerances": {}}


def test_dtype_baseline_identity_is_clean():
    assert not diff_dtype_baseline({"cell": _rec()}, _base())


@pytest.mark.parametrize("mutation,needle", [
    ({"accum": {"dot_general": {"sub_f32": 3, "f32_accum": 1, "f32": 0},
                "conv": {"sub_f32": 3, "f32_accum": 0, "f32": 0}}},
     "accumulating below f32 grew"),
    ({"exp_log_sub_f32": 1}, "exp/log ops grew"),
    ({"cast_roundtrips": 1}, "round-trip cast chains grew"),
    ({"large_reductions": {"sub_f32": 1, "f32": 1}},
     "sub-f32 reductions grew"),
    ({"collective_dtypes": ["bfloat16", "float32"]},
     "precision cut on the wire"),
    ({"waivers": [WAIVER_BF16_TRUNK, WAIVER_BF16_WIRE]},
     "waiver set changed"),
    ({"casts": {"float32->bfloat16": 8, "bfloat16->float32": 8}},
     "cast count grew"),
])
def test_dtype_baseline_drift_classes_fire(mutation, needle):
    """Each banked numerics property is a fence: any growth (or, for
    casts, growth beyond the layout-noise tolerance) is rc 1."""
    findings = diff_dtype_baseline({"cell": _rec(**mutation)}, _base())
    assert any(f.check == "dtype-baseline" and needle in f.message
               for f in findings), [str(f) for f in findings]


def test_dtype_baseline_cell_membership():
    # a fresh cell not yet banked
    findings = diff_dtype_baseline({"new": _rec()}, _base(), subset=True)
    assert any("not in the committed baseline" in f.message
               for f in findings)
    # a banked cell missing from the audit: full run flags it, a declared
    # subset run (the tier-1 lean fixture) does not
    assert any("matrix shrank" in f.message
               for f in diff_dtype_baseline({}, _base()))
    assert not diff_dtype_baseline({}, _base(), subset=True)


# ------------------------------------------- D5 at the compiled tier --

_PROMOTED_HLO = """\
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024] parameter(0)
  %narrow = bf16[1024] convert(f32[1024] %p0)
  %widen = f32[1024] convert(bf16[1024] %narrow)
  %ar = f32[1024] all-reduce(f32[1024] %widen), replica_groups={}
  ROOT %r = f32[1024] add(f32[1024] %ar, f32[1024] %p0)
}
"""

# as the XLA of jax 0.9.0 prints it: operands without their types
_PROMOTED_HLO_UNTYPED = """\
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %narrow = bf16[1024]{0} convert(%p0), metadata={op_name="jit(f)/psum"}
  %widen = f32[1024]{0} convert(%narrow)
  %ar = f32[1024]{0} all-reduce(%widen), replica_groups={}
  ROOT %r = f32[1024]{0} add(%ar, %p0)
}
"""

# a widening convert of a value that was ALWAYS bf16 is not a promotion
# round-trip: the program asked for an f32 reduction of it
_WIDENED_ONLY_HLO = """\
ENTRY %main (p0: bf16[1024]) -> f32[1024] {
  %p0 = bf16[1024]{0} parameter(0)
  %same = bf16[1024]{0} convert(%p0)
  %widen = f32[1024]{0} convert(%same)
  ROOT %ar = f32[1024]{0} all-reduce(%widen), replica_groups={}
}
"""

_PLAIN_HLO = """\
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024] parameter(0)
  ROOT %ar = f32[1024] all-reduce(f32[1024] %p0), replica_groups={}
}
"""


@pytest.mark.parametrize("hlo,expected", [
    pytest.param(_PROMOTED_HLO, "bf16", id="typed-operands"),
    pytest.param(_PROMOTED_HLO_UNTYPED, "bf16", id="untyped-operands"),
    pytest.param(_WIDENED_ONLY_HLO, "f32", id="widened-only"),
    pytest.param(_PLAIN_HLO, "f32", id="plain"),
])
def test_wire_dtype_resolves_promotion_roundtrip(hlo, expected):
    """CPU XLA's f32-only reduction runtime materialises a requested bf16
    collective as convert(bf16)→all-reduce(f32)→convert-back; the table
    must charge the op at the SOURCE dtype the program asked for —
    whether or not the HLO text prints operand types."""
    assert collective_wire_dtypes(hlo) == {"all-reduce": {expected: 1}}


def test_wire_dtype_contract_fires_and_admits_declared():
    table = collective_wire_dtypes(_PROMOTED_HLO)
    findings = audit_wire_dtypes(table, "f32", "fixture")
    assert findings and findings[0].check == "dtype-wire"
    assert "declares wire_dtype=f32" in findings[0].message
    assert not audit_wire_dtypes(table, "bf16", "fixture")
    assert not audit_wire_dtypes(collective_wire_dtypes(_PLAIN_HLO),
                                 "f32", "fixture")


# -------------------------------------------- jit-registration lint --


def test_jit_lint_fires_on_unregistered_site():
    src = ("import jax\n"
           "fn = jax.jit(lambda x: x)\n"          # module level
           "def rogue():\n"
           "    return jax.jit(lambda x: x + 1)\n")
    findings = lint_jit_source(src, registered={"make_train_step"})
    assert len(findings) == 2
    assert all(f.check == "jit-registration" for f in findings)
    owners = {f.evidence["function"] for f in findings}
    assert owners == {None, "rogue"}


def test_jit_lint_admits_registered_and_delegates():
    src = ("import jax\n"
           "def make_train_step():\n"
           "    return jax.jit(lambda s, x: s)\n"
           "def _build_step():\n"                 # documented delegate
           "    return jax.jit(lambda s: s)\n")
    assert not lint_jit_source(src, registered={"make_train_step"})


def test_repo_jit_sites_all_registered():
    """The real train/steps.py audits clean (also enforced session-wide by
    the conftest guard — this is the named, greppable assertion)."""
    assert not lint_jit_sites()


# ----------------------------------------------------- real repo half --


def test_repo_lean_cells_audit_clean(dtype_audit):
    assert set(dtype_audit.records) == _LEAN_CELLS
    assert not dtype_audit.findings, \
        [str(f) for f in dtype_audit.findings]


def test_repo_lean_cells_match_committed_baseline(dtype_audit):
    base = baselib.load_baseline()
    findings = diff_dtype_baseline(dtype_audit.records, base, subset=True)
    assert not findings, [str(f) for f in findings]


def test_bf16_cells_report_the_recipe(dtype_audit):
    rec = dtype_audit.records
    # the f32-pinned audit config has zero sub-f32 dot work; the shipped
    # bf16 cells are all-bf16 trunk (FLOP-weighted)
    assert rec["train_step"]["bf16_op_fraction"] == 0.0
    assert rec["train_step#bf16"]["bf16_op_fraction"] == 1.0
    # the banked trunk table: bf16 convs accumulate per the declared
    # waiver; any growth beyond these counts is a baseline finding
    assert rec["train_step#bf16"]["accum"]["conv"]["sub_f32"] > 0
    # serve softmax stays f32 under a bf16 trunk (the D4 customer)
    assert rec["topk_predict_serve#bf16"]["exp_log_sub_f32"] == 0
    # flax LN statistics stay f32 even under --ln_bf16 at audit width
    assert rec["vit_eval#ln_bf16"]["large_reductions"]["sub_f32"] == 0


def test_bf16_wire_cell_declares_its_collective(dtype_audit):
    rec = dtype_audit.records["train_step_bf16_reduce#bf16"]
    assert "bfloat16" in rec["collective_dtypes"]
    assert WAIVER_BF16_WIRE in rec["waivers"]
    assert WAIVER_BF16_TRUNK in rec["waivers"]


def test_master_weights_stay_f32_under_bf16_compute(dtype_audit):
    """The D2 contract on the real shipped-precision train step: no
    master-weights finding means every params/opt_state leaf is f32 both
    directions and the optimizer update computes at f32 — with the trunk
    at bf16. (The invariant the whole recipe hangs on.)"""
    assert not [f for f in dtype_audit.findings
                if f.check == "dtype-master"]


@pytest.mark.slow
def test_full_dtype_matrix_matches_baseline(dtype_audit):
    """Every registry cell (the wrapped step registry + the precision
    cells), audited clean and fenced against the committed baseline —
    what scripts/lint.sh runs in CI."""
    findings, records = audit_dtype_registry(dtype_audit.ctx)
    assert not findings, [str(f) for f in findings]
    base = baselib.load_baseline()
    drift = diff_dtype_baseline(records, base)
    assert not drift, [str(f) for f in drift]
    assert set(records) == set(base["dtype_programs"])


def test_committed_baseline_has_dtype_sections():
    """The checked-in artifact carries the dtype fence: the cells, the
    tolerance knob, and per-sharded-cell wire_dtypes tables."""
    base = baselib.load_baseline()
    assert len(base["dtype_programs"]) >= 15
    assert "cast_growth_pct" in base["tolerances"]
    sharded = base["programs"]["train_step_bf16@dp2"]
    assert "bf16" in sharded["wire_dtypes"].get("all-reduce", {})


# --------------------------------------------- one-program evidence --


def test_step_dtype_evidence_shape():
    a = jnp.zeros((8, 8), jnp.float32)
    ev = step_dtype_evidence(lambda a, b: a @ b, (a, a))
    assert ev == {"bf16_op_fraction": 0.0, "accum_dtype_ok": True}
    b = a.astype(jnp.bfloat16)
    ev = step_dtype_evidence(lambda a, b: a @ b, (b, b))
    assert ev["bf16_op_fraction"] == 1.0      # trunk matmuls are declared
    assert ev["accum_dtype_ok"] is True       # ...and not an unwaivable


# -------------------------------------------------------- parity pins --


def test_resnet_pool_seam_parity_pin():
    """The real D6 finding this PR fixed: the resnet global-average-pool
    fed the f32 head through a bf16 rounding (jnp.mean accumulates f32
    internally, then rounded back to bf16). The FIXED seam must equal the
    all-f32 seam to 2e-4; the OLD recipe must NOT — the pin bites."""
    import ddp_classification_pytorch_tpu.models.resnet as rn

    model = rn.resnet18(num_classes=10, variant="cifar",
                        dtype=jnp.bfloat16)
    x = jax.random.uniform(jax.random.PRNGKey(0), (8, 32, 32, 3),
                           jnp.float32)
    variables = model.init(jax.random.PRNGKey(1), x, train=False)
    logits, st = model.apply(variables, x, train=False,
                             capture_intermediates=True,
                             mutable=["intermediates"])
    trunk = st["intermediates"]["layer4_block1"]["__call__"][0]
    assert trunk.dtype == jnp.bfloat16
    W = variables["params"]["fc"]["kernel"]
    b = variables["params"]["fc"]["bias"]

    ref = jnp.mean(trunk.astype(jnp.float32), axis=(1, 2)) @ W + b
    fixed = jnp.mean(trunk, axis=(1, 2), dtype=jnp.float32) @ W + b
    old = jnp.mean(trunk, axis=(1, 2)).astype(jnp.float32) @ W + b

    # the manual fixed seam IS the model's seam (no hidden math between)
    assert float(jnp.max(jnp.abs(fixed - logits))) == 0.0
    assert float(jnp.max(jnp.abs(fixed - ref))) <= 2e-4
    assert float(jnp.max(jnp.abs(old - ref))) > 2e-4


def test_vit_ln_final_seam_parity_pin():
    """Same shape of finding in the ViT head: ln_final + token pool used
    to round through bf16 on the way into the f32 fc — including under
    --ln_bf16, where a bf16 ln_final bought no matmul throughput at all
    (its output feeds only the pool/head)."""
    from ddp_classification_pytorch_tpu.models import vit as vitlib

    model = vitlib.build_vit("vit_t16", num_classes=10,
                             dtype=jnp.bfloat16, ln_bf16=True)
    x = jax.random.uniform(jax.random.PRNGKey(0), (4, 32, 32, 3),
                           jnp.float32)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)
    logits, st = model.apply(variables, x, train=False,
                             capture_intermediates=True,
                             mutable=["intermediates"])
    ln = st["intermediates"]["ln_final"]["__call__"][0]
    # THE fix: ln_final stays f32 even under --ln_bf16
    assert ln.dtype == jnp.float32
    W = variables["params"]["fc"]["kernel"]
    b = variables["params"]["fc"]["bias"]

    fixed = ln.mean(axis=1) @ W + b
    old = ln.astype(jnp.bfloat16).mean(axis=1).astype(jnp.float32) @ W + b

    assert float(jnp.max(jnp.abs(fixed - logits))) == 0.0
    assert float(jnp.max(jnp.abs(old - fixed))) > 2e-4


@pytest.mark.slow  # two real train-step compiles (~20 s) for one assert
def test_bf16_wire_one_step_parity(dtype_audit):
    """The declared bf16 grad wire (D5's one admitted waiver) after ONE
    real train step: params land within 1e-3 of the f32-wire run (lr ×
    bf16 grad rounding), and NOT bit-identical — the wire is live."""
    from ddp_classification_pytorch_tpu.train.state import (
        create_train_state,
    )
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    ctx = dtype_audit.ctx
    imgs = jax.random.uniform(jax.random.PRNGKey(0), (8, 32, 32, 3),
                              jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 8)
    out_params = {}
    for wire in ("float32", "bfloat16"):
        cfg = ctx.tiny_cfg("baseline")
        cfg.model.dtype = "bfloat16"
        cfg.parallel.grad_reduce_dtype = wire
        model, tx, state = create_train_state(cfg, ctx.mesh,
                                              steps_per_epoch=4)
        step = make_train_step(cfg, model, tx, mesh=ctx.mesh)
        out = step(state, imgs, labels)
        new_state = out[0] if isinstance(out, tuple) else out
        out_params[wire] = new_state.params
    deltas = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        out_params["float32"], out_params["bfloat16"]))
    assert 0.0 < max(deltas) <= 1e-3, max(deltas)
