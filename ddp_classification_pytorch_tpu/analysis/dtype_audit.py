"""Dtype-flow audit: machine-checked numerics contracts over every program.

The framework's mixed-precision recipe — f32 master params/optimizer state,
bf16 compute (`model.dtype`), f32 loss head, the bf16 grad-wire round-trip
with f32 accumulation — was enforced only by convention and a handful of
parity pins. This pass turns each convention into an asserted property of
the TRACED program (the jaxpr), the same way `sharding_audit` did for
collectives. The contract catalogue:

- **D1 f64-free** — no float64/complex128 aval anywhere in a hot program.
  A NumPy f64 scalar leaking into a jit silently promotes on CPU (where
  x64 may be enabled) and diverges TPU-vs-CPU parity.
- **D2 master weights** — every params/opt_state leaf entering AND leaving
  a train step is f32, and the direct producers of the opt_state outputs
  compute at f32 (a bf16 hop in the optimizer update is the classic
  silent-divergence regression).
- **D3 accumulation** — a `dot_general`/`conv` with sub-f32 operands must
  accumulate in f32 (`preferred_element_type`), and any plain reduction
  over ≥ `REDUCE_ELEMS` sub-f32 elements must be f32 — unless the cell
  declares the matching waiver. Trunk matmuls of a bf16-compute model are
  the DECLARED design (MXU tiles accumulate f32 in hardware; the recipe
  banks inter-tile bf16 rounding for 2× MXU throughput), so bf16 cells
  carry `bf16_trunk_matmul` and the per-cell accumulation TABLE is banked
  in the baseline instead: a new bf16-accumulating op is drift, rc 1.
- **D4 loss head** — `exp`/`log`-family math (softmax, log-softmax,
  cross-entropy, the serve top-k's in-jit softmax, ArcFace margin trig)
  computes in f32; sub-f32 transcendentals need the `bf16_softmax` waiver.
- **D5 wire dtype** — the ONLY sub-f32 collective admitted is the declared
  `grad_reduce_dtype=bfloat16` round-trip (`bf16_wire` waiver). Checked at
  the jaxpr level here for the explicit-collective programs; the compiled
  (GSPMD) cells get the same contract via `sharding_audit`'s per-cell
  `wire_dtypes` record, which this PR promotes from evidence to contract.
- **D6 cast hygiene** — a no-op round-trip cast chain (f32→bf16→f32 with
  no compute between) only destroys mantissa bits; a float downcast of an
  integer/label path (int→bf16/f16) corrupts class indices ≥ 256. Both
  are findings, never waivable.

Waivers are DECLARED per cell (`DtypeCase.waivers`, catalogue in
`WAIVER_REASONS` and docs/analysis.md) — `--ln_bf16`'s LayerNorm-in-bf16
lever rides the same table (`ln_bf16` cell) instead of being implicit.
Per-cell summaries (cast counts, bf16-op fraction, accumulation table,
collective dtypes) bank into `analysis/baselines.json` under
`dtype_programs`; `cli.analyze --dtype --diff-baseline` (scripts/lint.sh)
fails CI on numerics drift.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import Finding
from .jaxpr_audit import (
    COLLECTIVE_PRIMITIVES,
    AuditContext,
    _sub_jaxprs,
    abstract_state,
    batch_sharded,
    build_registry,
)

# ---------------------------------------------------------------- contracts --

# sub-f32 floats: the compute dtypes the recipe trades precision for
_SUB_F32 = frozenset({"bfloat16", "float16", "float8_e4m3fn", "float8_e5m2"})
_F64 = frozenset({"float64", "complex128"})

# D3: a plain sum/product reduction folding at least this many sub-f32
# elements visibly loses mantissa (bf16 has 8 bits); smaller reductions
# (LayerNorm over a tiny hidden dim, pooling windows) are in-family
REDUCE_ELEMS = 4096

_DOT_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
_REDUCE_PRIMS = frozenset({"reduce_sum", "reduce_prod", "reduce_window_sum",
                           "cumsum"})
# D4: transcendental family of every softmax/log-softmax/CE/margin head
_EXP_LOG_PRIMS = frozenset({"exp", "exp2", "expm1", "log", "log1p",
                            "logistic", "acos", "atan2"})

# --------------------------------------------------------------- waivers --

WAIVER_BF16_TRUNK = "bf16_trunk_matmul"
WAIVER_BF16_WIRE = "bf16_wire"
WAIVER_BF16_SOFTMAX = "bf16_softmax"
WAIVER_BF16_REDUCE = "bf16_reduce"
WAIVER_LN_BF16 = "ln_bf16"

# the declared-waiver catalogue: every token a DtypeCase may carry, with
# the reviewed reason — mirrored in docs/analysis.md so an undocumented
# waiver cannot land silently (tests/test_dtype_audit.py locks the mirror)
WAIVER_REASONS: Dict[str, str] = {
    WAIVER_BF16_TRUNK:
        "model-trunk matmuls/convs run bf16-in/bf16-out by design "
        "(`model.dtype`): MXU tiles accumulate f32 in hardware and the "
        "master params stay f32 — the banked accumulation table fences "
        "the op set instead",
    WAIVER_BF16_WIRE:
        "the declared grad_reduce_dtype=bfloat16 round-trip: gradients "
        "cast to bf16 for ONE pmean and back, f32 accumulation on both "
        "sides (train/steps.py::_reduced_grad_section)",
    WAIVER_BF16_SOFTMAX:
        "a softmax deliberately run below f32 — no shipped program "
        "carries this today; it exists so the detector is waivable-by-"
        "declaration rather than by code edit",
    WAIVER_BF16_REDUCE:
        "a large reduction deliberately run below f32 — reserved, "
        "no shipped program carries it",
    WAIVER_LN_BF16:
        "`--ln_bf16` (ViT): LayerNorm affine/output in the block compute "
        "dtype (statistics stay f32 inside flax) — parity pinned by "
        "tests/test_vit.py::test_ln_bf16_stays_close_to_f32_recipe; "
        "implies `bf16_reduce` for the LN reductions at flagship widths",
}

# tokens that subsume other tokens for detector purposes
_WAIVER_IMPLIES = {WAIVER_LN_BF16: frozenset({WAIVER_BF16_REDUCE})}


def _effective_waivers(waivers: FrozenSet[str]) -> FrozenSet[str]:
    out = set(waivers)
    for w in waivers:
        out |= _WAIVER_IMPLIES.get(w, frozenset())
    return frozenset(out)


# ------------------------------------------------------------ jaxpr walking --

def _iter_bodies(jaxpr):
    """Every jaxpr body reachable from `jaxpr` (jit/scan/cond/shard_map/
    remat inners included), outermost first."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(_sub_jaxprs(eqn))


def _dt(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _is_float(name: Optional[str]) -> bool:
    return name is not None and (name.startswith("float")
                                 or name.startswith("bfloat"))


def _elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", ())
    return int(np.prod(shape, dtype=np.int64)) if shape else 1


def _dot_flops(eqn) -> float:
    """2·K per output element — the MFU-relevant weight of one dot/conv.
    Falls back to the output size when the contraction size cannot be
    recovered (never raises: the weight only shapes a fraction)."""
    out = float(_elems(eqn.outvars[0]))
    try:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs_shape = eqn.invars[0].aval.shape
            k = float(np.prod([lhs_shape[i] for i in lc], dtype=np.float64))
        else:  # conv: K = kernel elements per output feature
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            k = float(np.prod(rhs, dtype=np.float64)) / rhs[dn.rhs_spec[0]]
        return 2.0 * k * out
    except Exception:
        return out


# ----------------------------------------------------------------- the pass --

@dataclass
class DtypeCase:
    """One audited (program, precision-config) cell.

    `train` turns on the D2 master-weights contract (params/opt_state leaf
    dtypes both directions + f32 producers of the opt_state outputs).
    `waivers` is the cell's DECLARED subset of `WAIVER_REASONS` — an
    undeclared violation is a finding; a declared one is banked in the
    baseline summary instead."""

    name: str
    build: Callable[[AuditContext], Tuple[Any, Tuple[Any, ...]]]
    train: bool = False
    waivers: FrozenSet[str] = frozenset()
    note: str = ""
    evidence: Dict[str, Any] = dc_field(default_factory=dict)


def _path_has(path, *needles: str) -> bool:
    s = jax.tree_util.keystr(path)
    return any(n in s for n in needles)


def _audit_state_leaves(tree, where: str, direction: str) -> List[Finding]:
    """D2 leaf check over one side of a train step: every float leaf under
    a params/opt_state path must be f32 (integer leaves — step counts,
    schedule indices — are fine)."""
    findings: List[Finding] = []
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        if not _path_has(path, "params", "opt_state"):
            continue
        dt = str(getattr(leaf, "dtype", ""))
        if _is_float(dt) and dt != "float32":
            findings.append(Finding(
                "dtype-master", where,
                f"{direction} leaf `{jax.tree_util.keystr(path)}` is {dt}, "
                "not float32 — the master-weights invariant (f32 params/"
                "optimizer state) is broken; bf16 belongs in compute casts, "
                "never in the stored state",
                {"path": jax.tree_util.keystr(path), "dtype": dt,
                 "direction": direction}))
    return findings


def _innermost(jaxpr):
    """Peel single-eqn jit wrappers (a jitted fn traced by make_jaxpr is
    one jit eqn) down to the body whose outvars positionally match the
    flattened outputs."""
    while (len(jaxpr.eqns) == 1
           and jaxpr.eqns[0].primitive.name == "jit"
           and len(jaxpr.eqns[0].outvars) == len(jaxpr.outvars)):
        jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr
    return jaxpr


def _audit_opt_producers(closed, fn, args, where: str) -> List[Finding]:
    """D2 producer check: the eqns that directly produce the opt_state
    outputs must take only f32 float inputs — a sub-f32 operand there
    means the optimizer update itself computed below f32."""
    findings: List[Finding] = []
    try:
        out_shape = jax.eval_shape(fn, *args)
    except Exception:
        return findings
    leaves, _ = jax.tree_util.tree_flatten_with_path(out_shape)
    body = _innermost(closed.jaxpr)
    if len(body.outvars) != len(leaves):
        return findings
    producers: Dict[int, Any] = {}
    for eqn in body.eqns:
        for ov in eqn.outvars:
            producers[id(ov)] = eqn
    for i, (path, _) in enumerate(leaves):
        if not _path_has(path, "opt_state"):
            continue
        eqn = producers.get(id(body.outvars[i]))
        if eqn is None:
            continue
        bad = sorted({_dt(v) for v in eqn.invars
                      if _is_float(_dt(v)) and _dt(v) != "float32"
                      and _dt(v) is not None})
        if bad:
            findings.append(Finding(
                "dtype-master", where,
                f"opt_state output `{jax.tree_util.keystr(path)}` is "
                f"produced by `{eqn.primitive.name}` with {bad} inputs — "
                "the optimizer update must compute at f32",
                {"path": jax.tree_util.keystr(path), "producer":
                 eqn.primitive.name, "input_dtypes": bad}))
    return findings


def audit_program(fn, args: Tuple[Any, ...], name: str = "<fixture>",
                  train: bool = False,
                  waivers: FrozenSet[str] = frozenset(),
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Trace one program and run the D1–D6 catalogue over its jaxpr;
    returns (findings, the banked summary record). The fixture-facing
    surface: tests prove each detector FIREs here without planting
    violating code in the package."""
    unknown = set(waivers) - set(WAIVER_REASONS)
    if unknown:
        raise ValueError(f"undeclared waiver token(s) {sorted(unknown)} — "
                         f"add to WAIVER_REASONS (and docs/analysis.md) "
                         "before use")
    waived = _effective_waivers(waivers)
    findings: List[Finding] = []

    closed = jax.make_jaxpr(fn)(*args)

    casts: Dict[str, int] = {}
    accum = {"dot_general": {"sub_f32": 0, "f32_accum": 0, "f32": 0},
             "conv": {"sub_f32": 0, "f32_accum": 0, "f32": 0}}
    reductions = {"sub_f32": 0, "f32": 0}
    collective_dtypes: set = set()
    exp_log_sub_f32 = 0
    roundtrips = 0
    n_eqns = 0
    f64_hits: List[str] = []
    dot_flops = {"sub_f32": 0.0, "total": 0.0}

    for v in list(closed.jaxpr.invars) + list(closed.jaxpr.constvars):
        if _dt(v) in _F64:
            f64_hits.append(f"program input/const {_dt(v)}"
                            f"{getattr(v.aval, 'shape', ())}")

    for body in _iter_bodies(closed.jaxpr):
        # per-body var → producing convert eqn, and consumer counts, for D6
        produced_by: Dict[int, Any] = {}
        consumers: Dict[int, int] = {}
        for eqn in body.eqns:
            for ov in eqn.outvars:
                produced_by[id(ov)] = eqn
            for iv in eqn.invars:
                consumers[id(iv)] = consumers.get(id(iv), 0) + 1

        for eqn in body.eqns:
            n_eqns += 1
            prim = eqn.primitive.name
            in_dts = [_dt(v) for v in eqn.invars]
            out_dts = [_dt(v) for v in eqn.outvars]

            # D1 — f64 anywhere
            for dt in in_dts + out_dts:
                if dt in _F64:
                    f64_hits.append(f"`{prim}` carries {dt}")

            if prim == "convert_element_type":
                src, dst = in_dts[0], out_dts[0]
                key = f"{src}->{dst}"
                casts[key] = casts.get(key, 0) + 1
                # D6a — no-op round trip: this convert restores the dtype
                # its (sole-use) operand was narrowed from
                inner = produced_by.get(id(eqn.invars[0]))
                if (inner is not None
                        and inner.primitive.name == "convert_element_type"
                        and _dt(inner.invars[0]) == dst
                        and src in _SUB_F32 and _is_float(dst)
                        and consumers.get(id(eqn.invars[0]), 0) == 1):
                    roundtrips += 1
                    findings.append(Finding(
                        "dtype-cast", name,
                        f"no-op round-trip cast chain {dst}→{src}→{dst} "
                        "with no compute between — only destroys mantissa "
                        "bits; delete both casts",
                        {"chain": f"{dst}->{src}->{dst}"}))
                # D6b — integer/label path downcast to a sub-f32 float
                if (src is not None and ("int" in src or src == "bool")
                        and dst in _SUB_F32):
                    findings.append(Finding(
                        "dtype-cast", name,
                        f"integer/label path downcast {src}→{dst}: class "
                        "indices ≥ 256 are not representable in bf16 — "
                        "labels must reach the loss at ≥ f32/int32",
                        {"src": src, "dst": dst}))

            elif prim in _DOT_PRIMS:
                kind = "dot_general" if prim == "dot_general" else "conv"
                sub = any(dt in _SUB_F32 for dt in in_dts if dt)
                fl = _dot_flops(eqn)
                dot_flops["total"] += fl
                if sub:
                    dot_flops["sub_f32"] += fl
                    if out_dts[0] == "float32":
                        accum[kind]["f32_accum"] += 1
                    else:
                        accum[kind]["sub_f32"] += 1
                        if WAIVER_BF16_TRUNK not in waived:
                            findings.append(Finding(
                                "dtype-accum", name,
                                f"`{prim}` with sub-f32 operands "
                                f"({[d for d in in_dts if d]}) accumulates "
                                f"to {out_dts[0]} without "
                                "preferred_element_type=f32 and without "
                                f"the `{WAIVER_BF16_TRUNK}` waiver",
                                {"primitive": prim, "in": in_dts,
                                 "out": out_dts[0]}))
                else:
                    accum[kind]["f32"] += 1

            elif prim in _REDUCE_PRIMS:
                sub = in_dts and in_dts[0] in _SUB_F32
                folded = (_elems(eqn.invars[0])
                          // max(_elems(eqn.outvars[0]), 1))
                if sub and folded >= REDUCE_ELEMS:
                    reductions["sub_f32"] += 1
                    if WAIVER_BF16_REDUCE not in waived:
                        findings.append(Finding(
                            "dtype-accum", name,
                            f"`{prim}` folds {folded} {in_dts[0]} elements "
                            f"below f32 (threshold {REDUCE_ELEMS}) — "
                            "accumulate in f32 or declare the "
                            f"`{WAIVER_BF16_REDUCE}` waiver",
                            {"primitive": prim, "folded": folded,
                             "dtype": in_dts[0]}))
                elif in_dts and _is_float(in_dts[0]):
                    reductions["f32"] += 1

            elif prim in _EXP_LOG_PRIMS:
                if any(dt in _SUB_F32 for dt in in_dts if dt):
                    exp_log_sub_f32 += 1
                    if WAIVER_BF16_SOFTMAX not in waived:
                        findings.append(Finding(
                            "dtype-loss-head", name,
                            f"`{prim}` computes at {in_dts[0]} — softmax/"
                            "log-softmax/CE/margin math must run at f32 "
                            "(cast the logits: the head is O(B·C), the "
                            "cast is free next to the matmuls)",
                            {"primitive": prim, "dtype": in_dts[0]}))

            elif prim in COLLECTIVE_PRIMITIVES:
                for dt in in_dts:
                    if not _is_float(dt):
                        continue
                    collective_dtypes.add(dt)
                    if dt in _SUB_F32 and WAIVER_BF16_WIRE not in waived:
                        findings.append(Finding(
                            "dtype-wire", name,
                            f"collective `{prim}` puts {dt} on the wire — "
                            "the only admitted sub-f32 collective is the "
                            "declared grad_reduce_dtype=bfloat16 round-"
                            f"trip (`{WAIVER_BF16_WIRE}` waiver)",
                            {"primitive": prim, "dtype": dt}))

    if f64_hits:
        findings.append(Finding(
            "dtype-f64", name,
            f"float64 in a hot program ({f64_hits[0]}"
            + (f" + {len(f64_hits) - 1} more" if len(f64_hits) > 1 else "")
            + ") — a NumPy scalar leak that silently promotes on CPU and "
            "diverges TPU-vs-CPU parity; cast at the source",
            {"sites": f64_hits[:8]}))

    if train:
        findings.extend(_audit_state_leaves(args, name, "input"))
        try:
            out_shape = jax.eval_shape(fn, *args)
            findings.extend(_audit_state_leaves(out_shape, name, "output"))
        except Exception:
            pass
        findings.extend(_audit_opt_producers(closed, fn, args, name))

    frac = (dot_flops["sub_f32"] / dot_flops["total"]
            if dot_flops["total"] else 0.0)
    summary = {
        "n_eqns": n_eqns,
        "casts": dict(sorted(casts.items())),
        "cast_roundtrips": roundtrips,
        "bf16_op_fraction": round(frac, 4),
        "accum": accum,
        "large_reductions": reductions,
        "exp_log_sub_f32": exp_log_sub_f32,
        "collective_dtypes": sorted(collective_dtypes),
        "waivers": sorted(waivers),
    }
    return findings, summary


# ------------------------------------------------- one-program evidence --

def step_dtype_evidence(fn, args: Tuple[Any, ...]) -> Dict[str, Any]:
    """Dtype evidence from one trace of an already-built step (called by
    tests/test_dtype_audit.py only; the matrix goes through `audit_program`
    itself — ROADMAP D7): `bf16_op_fraction` (FLOP-weighted fraction of
    dot/conv work with sub-f32 operands) and
    `accum_dtype_ok` (the UNWAIVABLE contracts hold: no f64, no large
    sub-f32 reduction, no sub-f32 exp/log, no round-trip cast chain —
    trunk bf16 matmuls are the declared design and report via the
    fraction, not this flag)."""
    findings, summary = audit_program(
        fn, args, name="<evidence>",
        waivers=frozenset({WAIVER_BF16_TRUNK, WAIVER_BF16_WIRE}))
    return {
        "bf16_op_fraction": summary["bf16_op_fraction"],
        "accum_dtype_ok": not findings,
    }


# --------------------------------------------------------------- registry --

def _bf16_state(ctx: AuditContext):
    """(cfg, model, tx, state) with `model.dtype=bfloat16` — the SHIPPED
    compute precision (resnet defaults bf16; the f32-pinned audit config
    exists for byte-exact sharding baselines). Cached on the shared ctx so
    the test suite's module-scoped audit pays the init once."""
    if "dtype:bf16" not in ctx._cache:
        from ..train.state import create_train_state

        cfg = ctx.tiny_cfg("baseline")
        cfg.model.dtype = "bfloat16"
        model, tx, state = create_train_state(cfg, ctx.mesh,
                                              steps_per_epoch=4)
        ctx._cache["dtype:bf16"] = (cfg, model, tx, state)
    return ctx._cache["dtype:bf16"]


def _build_train_bf16_compute(ctx: AuditContext):
    from ..train.steps import make_train_step

    cfg, model, tx, state = _bf16_state(ctx)
    fn = make_train_step(cfg, model, tx, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels())


def _build_eval_bf16_compute(ctx: AuditContext):
    from ..train.steps import make_eval_step

    cfg, model, _, state = _bf16_state(ctx)
    fn = make_eval_step(cfg, model, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels(), ctx.valid())


def _build_topk_serve_bf16_compute(ctx: AuditContext):
    """The serve hot path at shipped precision: bf16 trunk into the f32
    head, softmax + top-k in-jit — the D4 contract's main customer."""
    from ..train.steps import make_topk_predict_step

    cfg, model, _, state = _bf16_state(ctx)
    fn = make_topk_predict_step(cfg, model, k=3)
    return fn, (state, ctx.images())


def _build_train_bf16_wire_bf16_compute(ctx: AuditContext):
    """Both levers at once: bf16 trunk AND the bf16 grad wire — proves the
    waivers compose (f32 master state, one declared sub-f32 collective)."""
    from ..train.steps import make_train_step

    _, model, tx, state = _bf16_state(ctx)
    cfg = ctx.tiny_cfg("baseline")
    cfg.model.dtype = "bfloat16"
    cfg.parallel.grad_reduce_dtype = "bfloat16"
    fn = make_train_step(cfg, model, tx, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels())


def _build_train_accum_bf16_wire(ctx: AuditContext):
    """K=4 accumulation × bf16 grad wire on the composed dp2 mesh: the
    scan's f32 accumulator is the D2/D3 subject (it must never narrow,
    whatever the wire dtype), and the once-per-K pmean is the one
    declared sub-f32 collective (D5 via the `bf16_wire` waiver)."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.zero_opt = "off"
    cfg.parallel.grad_reduce_dtype = "bfloat16"
    cfg.parallel.grad_accum = 4
    mesh = ctx.composed_mesh("dp2")
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh, zero_opt="off"),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _build_vit_ln_bf16(ctx: AuditContext):
    """`--ln_bf16` as a DECLARED cell: ViT eval with the LayerNorms in the
    block compute dtype — the waiver that used to be implicit in a CLI
    flag now rides the contract table (parity pin: tests/test_vit.py)."""
    from ..train.state import create_train_state
    from ..train.steps import make_eval_step

    if "dtype:vit_ln_bf16" not in ctx._cache:
        cfg = ctx.tiny_cfg("baseline")
        cfg.model.arch = "vit_t16"
        cfg.model.dtype = "bfloat16"
        cfg.model.ln_bf16 = True
        model, tx, state = create_train_state(cfg, ctx.mesh,
                                              steps_per_epoch=4)
        ctx._cache["dtype:vit_ln_bf16"] = (cfg, model, state)
    cfg, model, state = ctx._cache["dtype:vit_ln_bf16"]
    fn = make_eval_step(cfg, model, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels(), ctx.valid())


def dtype_registry() -> List[DtypeCase]:
    """Every audited (program, precision-config) cell.

    NOTE (mirrors jaxpr_audit.build_registry): wrapping the step registry
    means a NEW registered step factory is dtype-audited automatically —
    no second registration. Cells whose precision config differs from the
    f32-pinned audit default (`#bf16`, `#ln_bf16` suffixes) are added
    explicitly below; a new precision KNOB needs a new cell here plus a
    waiver entry if it trades precision."""
    cases: List[DtypeCase] = []
    for spec in build_registry():
        train = spec.name.startswith("train_step")
        waivers = (frozenset({WAIVER_BF16_WIRE})
                   if spec.name == "train_step_bf16_reduce" else frozenset())
        cases.append(DtypeCase(spec.name, spec.build, train=train,
                               waivers=waivers))
    cases += [
        DtypeCase("train_step#bf16", _build_train_bf16_compute, train=True,
                  waivers=frozenset({WAIVER_BF16_TRUNK}),
                  note="shipped compute precision (model.dtype=bfloat16)"),
        DtypeCase("eval_step#bf16", _build_eval_bf16_compute,
                  waivers=frozenset({WAIVER_BF16_TRUNK})),
        DtypeCase("topk_predict_serve#bf16", _build_topk_serve_bf16_compute,
                  waivers=frozenset({WAIVER_BF16_TRUNK}),
                  note="serve softmax must stay f32 under a bf16 trunk"),
        DtypeCase("train_step_bf16_reduce#bf16",
                  _build_train_bf16_wire_bf16_compute, train=True,
                  waivers=frozenset({WAIVER_BF16_TRUNK, WAIVER_BF16_WIRE}),
                  note="bf16 trunk + bf16 grad wire compose"),
        DtypeCase("train_step_accum4#accum_bf16",
                  _build_train_accum_bf16_wire, train=True,
                  waivers=frozenset({WAIVER_BF16_WIRE}),
                  note="K=4 scan accumulator stays f32 under the bf16 "
                       "wire; one declared sub-f32 collective per "
                       "optimizer step"),
        DtypeCase("vit_eval#ln_bf16", _build_vit_ln_bf16,
                  waivers=frozenset({WAIVER_BF16_TRUNK, WAIVER_LN_BF16}),
                  note="--ln_bf16 as a declared waiver, not an implicit flag"),
    ]
    return cases


def audit_dtype_case(case: DtypeCase, ctx: AuditContext
                     ) -> Tuple[List[Finding], Dict[str, Any]]:
    fn, args = case.build(ctx)
    findings, summary = audit_program(fn, args, name=case.name,
                                      train=case.train, waivers=case.waivers)
    case.evidence.update(summary)
    return findings, summary


def audit_dtype_registry(ctx: Optional[AuditContext] = None,
                         cases: Optional[List[DtypeCase]] = None
                         ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit every dtype cell; returns (findings, {cell: summary}) — the
    records feed the `dtype_programs` baseline section."""
    ctx = ctx or AuditContext()
    records: Dict[str, Any] = {}
    findings: List[Finding] = []
    for case in (cases if cases is not None else dtype_registry()):
        f, rec = audit_dtype_case(case, ctx)
        findings += f
        records[case.name] = rec
    return findings, records


# --------------------------------------------------------- baseline diff --

# dtype drift tolerances (merged into the baseline's `tolerances` block):
# cast-count churn within this band is layout noise; everything else in
# the dtype record is zero-tolerance (each drifted field is a reviewed-
# precision property, not a size)
DTYPE_TOLERANCES: Dict[str, float] = {"cast_growth_pct": 25.0}


def diff_dtype_baseline(records: Dict[str, Any], baseline: Dict[str, Any],
                        tolerances: Optional[Dict[str, float]] = None,
                        subset: bool = False) -> List[Finding]:
    """Fresh dtype summaries vs the committed `dtype_programs` section →
    findings for every numerics drift: a new sub-f32-accumulating op, a
    new sub-f32 transcendental/reduction/collective dtype, a waiver set
    change, cast-count growth beyond tolerance, and (unless `subset`)
    cells appearing/disappearing."""
    tol = {**DTYPE_TOLERANCES, **(baseline.get("tolerances") or {}),
           **(tolerances or {})}
    base_cells = baseline.get("dtype_programs", {})
    findings: List[Finding] = []

    for key, rec in sorted(records.items()):
        base = base_cells.get(key)
        if base is None:
            findings.append(Finding(
                "dtype-baseline", key,
                "dtype cell not in the committed baseline — bank it with "
                "--update-baseline (and review the summary) before CI can "
                "fence it"))
            continue
        for kind in ("dot_general", "conv"):
            cur = rec["accum"][kind]["sub_f32"]
            was = base.get("accum", {}).get(kind, {}).get("sub_f32", 0)
            if cur > was:
                findings.append(Finding(
                    "dtype-baseline", key,
                    f"{kind} ops accumulating below f32 grew {was} → {cur} "
                    "— every new one is an unreviewed precision loss "
                    "(set preferred_element_type=f32 or regenerate the "
                    "baseline with the change reviewed)",
                    {"kind": kind, "base": was, "current": cur}))
        for field, label in (("exp_log_sub_f32", "sub-f32 exp/log ops"),
                             ("cast_roundtrips", "round-trip cast chains")):
            cur, was = rec[field], base.get(field, 0)
            if cur > was:
                findings.append(Finding(
                    "dtype-baseline", key,
                    f"{label} grew {was} → {cur}",
                    {"base": was, "current": cur}))
        cur_red = rec["large_reductions"]["sub_f32"]
        was_red = base.get("large_reductions", {}).get("sub_f32", 0)
        if cur_red > was_red:
            findings.append(Finding(
                "dtype-baseline", key,
                f"large sub-f32 reductions grew {was_red} → {cur_red}",
                {"base": was_red, "current": cur_red}))
        new_wire = (set(rec["collective_dtypes"])
                    - set(base.get("collective_dtypes", []))) & _SUB_F32
        if new_wire:
            findings.append(Finding(
                "dtype-baseline", key,
                f"new sub-f32 collective wire dtype(s) {sorted(new_wire)} "
                "vs baseline — an undeclared precision cut on the wire",
                {"new": sorted(new_wire)}))
        if sorted(rec["waivers"]) != sorted(base.get("waivers", [])):
            findings.append(Finding(
                "dtype-baseline", key,
                f"waiver set changed {base.get('waivers', [])} → "
                f"{rec['waivers']} — waiver changes must be banked via "
                "--update-baseline with the diff reviewed",
                {"base": base.get("waivers", []),
                 "current": rec["waivers"]}))
        cur_casts = sum(rec["casts"].values())
        was_casts = sum(base.get("casts", {}).values())
        if was_casts and cur_casts > was_casts * (
                1 + tol["cast_growth_pct"] / 100.0):
            findings.append(Finding(
                "dtype-baseline", key,
                f"cast count grew {was_casts} → {cur_casts} "
                f"(tolerance {tol['cast_growth_pct']}%) — cast churn "
                "beyond layout noise usually hides a new precision seam",
                {"base": was_casts, "current": cur_casts}))

    if not subset:
        for key in sorted(set(base_cells) - set(records)):
            findings.append(Finding(
                "dtype-baseline", key,
                "baseline dtype cell missing from the fresh audit — the "
                "matrix shrank; if intentional, regenerate with "
                "--update-baseline"))
    return findings
