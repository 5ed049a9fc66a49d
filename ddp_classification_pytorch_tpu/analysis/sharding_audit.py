"""SPMD sharding & communication audit of the compiled step programs.

The jaxpr audit (jaxpr_audit.py) proves donation, callback-freedom, and the
uint8 epilogue — but says nothing about the properties that decide step
time on a pod: which collectives GSPMD actually inserted, how big their
payloads are, whether params/optimizer state ended up replicated or
sharded, and peak HBM. This pass lowers the registry's programs on the
composed multi-device audit meshes (`parallel.mesh.composed_audit_meshes`:
dp-only 2×1 and dp×tp 2×2) and extracts three evidence families from each
compiled executable:

- **collective inventory** — every `all-reduce` / `all-gather` /
  `reduce-scatter` / `collective-permute` / `all-to-all` op in the HLO
  text, with per-device payload bytes per step and the MESH AXIS it runs
  over (attributed by matching `replica_groups` — both the explicit
  `{{0,2},{1,3}}` and the iota `[2,2]<=[2,2]T(1,0)` forms — against the
  partitions each mesh axis induces on the device ordinals).
- **sharding table** — the executable's `input_shardings` (post-GSPMD
  truth, not the request) per input leaf, flagging large buffers
  replicated across the data axis (the ZeRO opportunity/regression
  detector) and implicit weight resharding (a big all-gather inside the
  step — the accidental MFU eater).
- **memory budget** — argument/output/temp/alias bytes from
  `memory_analysis()` and the derived `peak_hbm_bytes`
  (arg + out + temp − alias), generalizing the donation evidence.

Per-program **comms policies** turn the inventory into findings: the dp
train step must carry the gradient all-reduce set (data-axis all-reduce
bytes ≥ the parameter bytes) and NOTHING else; eval/serve programs stay
collective-free up to the scalar metric reductions (per-op payload under
`SMALL_COLLECTIVE_BYTES`) their device-side accumulation design implies.

`analysis/baseline.py` persists the records per (program, mesh, config)
into the checked-in `analysis/baselines.json`; `cli.analyze
--diff-baseline` turns drift beyond tolerances into rc 1 findings.

Everything here is CPU-pinned host-side analysis — payloads and shardings
are topology properties of the lowered program, identical on the TPU the
program will actually run on (per-device local shapes scale with the real
mesh, which is why the audit meshes are FIXED 2×1/2×2 compositions: the
baseline must not depend on the host's device count).
"""

from __future__ import annotations

import re
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import Finding
from .jaxpr_audit import (
    AuditContext,
    _DTYPE_BYTES,
    abstract_state,
    batch_sharded,
)

# collective op kinds extracted from HLO (async `-start` halves carry the
# payload; `-done` is payload-free and deliberately NOT matched below)
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

# per-op payload allowed in a "collective-free" program: the scalar metric
# reductions (loss/topk sums over the sharded batch) that device-side eval
# accumulation implies. Calibrated ~8× above the worst legitimate op
# observed (nested-eval's top-k vectors, ≤2 KiB) and far below any
# weight/activation payload at real scale.
SMALL_COLLECTIVE_BYTES = 16 * 1024

# an all-gather at/above this per-op payload is weight (not control)
# traffic: implicit resharding of a parameter inside the step
RESHARD_BYTES = 256 * 1024

# ZeRO detector: an input buffer this large replicated across a >1 data
# axis is optimizer/param state the data axis could shard. Above the
# audit config's largest legitimate leaf (~9.4 MB conv kernel) so the
# repo audits clean until state sharding actually lands (ROADMAP).
REPLICATED_BYTES = 16 * 1024 * 1024


# ---------------------------------------------------------- HLO parsing --

# `%name = <shape> all-reduce(...)` — shape is a single array literal or a
# tuple of them; `(?:-start)?` admits the async halves, and the mandatory
# `(` right after keeps `-done` ops (payload-free) out.
_OP_RE = re.compile(
    r"=\s*(?P<shape>\((?:[^()]|\([^)]*\))*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)"
    r"\s*(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(?P<explicit>\{\{[\d, ]*(?:\},\{[\d, ]*)*\}\}"
    r"|\{\})"
    r"|replica_groups=\[(?P<gshape>[\d,]+)\]<=\[(?P<src>[\d,]+)\]"
    r"(?:T\((?P<perm>[\d,]+)\))?"
)


def parse_replica_groups(attr: str) -> Optional[frozenset]:
    """`replica_groups=...` → frozenset of frozensets of device ordinals.

    Handles both textual forms XLA emits: the explicit list
    `{{0,2},{1,3}}` and the iota form `[2,2]<=[4]` /
    `[2,2]<=[2,2]T(1,0)` (ids = arange(prod(src)).reshape(src)
    .transpose(perm).reshape(groups, group_size)). Returns None when the
    op carries no replica_groups attribute."""
    m = _GROUPS_RE.search(attr)
    if not m:
        return None
    if m.group("explicit") is not None:
        # scan the raw literal: `\{...\}` matches each INNER group of
        # `{{0,2},{1,3}}` (the outer braces never enclose a digit run);
        # `{}` yields no non-empty group — the all-devices shorthand
        groups = [g for g in re.findall(r"\{([\d, ]*)\}",
                                        m.group("explicit")) if g.strip()]
        if not groups:
            return frozenset()
        return frozenset(
            frozenset(int(x) for x in g.replace(" ", "").split(",") if x)
            for g in groups)
    gshape = [int(x) for x in m.group("gshape").split(",")]
    src = [int(x) for x in m.group("src").split(",")]
    ids = np.arange(int(np.prod(src))).reshape(src)
    if m.group("perm"):
        ids = ids.transpose([int(x) for x in m.group("perm").split(",")])
    ids = ids.reshape(gshape)
    return frozenset(frozenset(int(x) for x in row) for row in ids)


def _axis_groupings(mesh) -> Dict[str, frozenset]:
    """Axis-subset label → the partition of device ordinals a collective
    over exactly those mesh axes produces ('data', 'model', 'data+model',
    …; the full-mesh subset also registers as 'all'). Ordinals index
    `mesh.devices` in row-major order — the device-assignment order jit
    uses — which is how HLO replica_groups number participants. Combined
    subsets matter: with params replicated over BOTH axes of a dp×tp
    mesh, XLA reduces gradients over the whole mesh in one op, so the
    gradient all-reduce floor must count every partition that spans the
    data axis."""
    from itertools import combinations

    shape = mesh.devices.shape
    names = [str(n) for n in mesh.axis_names]
    n = int(np.prod(shape))
    idx = np.arange(n).reshape(shape)
    out: Dict[str, frozenset] = {}
    for r in range(1, len(names) + 1):
        for axes in combinations(range(len(names)), r):
            rest = [k for k in range(len(names)) if k not in axes]
            rows = idx.transpose(rest + list(axes)).reshape(
                -1, int(np.prod([shape[k] for k in axes])))
            label = ("all" if len(axes) == len(names)
                     else "+".join(names[k] for k in axes))
            out[label] = frozenset(
                frozenset(int(x) for x in row) for row in rows)
    return out


def _spans_data(label: str) -> bool:
    """Whether an attribution label reduces over the data axis."""
    from ..parallel.mesh import DATA_AXIS

    return label == "all" or DATA_AXIS in label.split("+")


# CPU XLA's reduction runtime is f32-only: a program that puts a narrower
# dtype on the wire (parallel.grad_reduce_dtype=bfloat16) compiles as
# convert(f32→bf16) → convert(bf16→f32) → collective(f32), the round-trip
# pair usually folded into the kLoop fusion feeding the collective.
# Counting the f32 shape would erase exactly the payload halving the bf16
# reduction exists to buy (TPU ships the collective at bf16 natively), so
# the inventory resolves each collective operand — through at most one
# fusion — to such a round-trip and charges it at the SOURCE dtype.
# HLO text prints operands with their types (`convert(f32[8]{0} %x)`) or
# without (`convert(%x)`) depending on the XLA build, so dtypes are read
# from each instruction's own RESULT type, which both forms carry.
_DEF_RE = re.compile(r"%(?P<name>[\w.-]+)\s*=\s*(?P<dtype>[a-z0-9]+)\[")
_CONVERT_RE = re.compile(
    r"%(?P<name>[\w.-]+)\s*=\s*(?P<dst>[a-z0-9]+)\[[\d,]*\]"
    r"(?:\{[^}]*\})?\s*convert\((?:[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?\s+)?"
    r"%(?P<op>[\w.-]+)\)")
_FUSION_RE = re.compile(
    r"%(?P<name>[\w.-]+)\s*=\s*[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?\s*"
    r"fusion\(.*\bcalls=%(?P<comp>[\w.-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%(?P<name>[\w.-]+)\s*\(")
_OPERAND_RE = re.compile(r"\(([^)]*)\)")


def _wire_dtypes(hlo_text: str) -> Dict[str, str]:
    """Instruction name → the element type its value round-tripped
    through right before use: widening converts whose operand is the
    matching narrowing convert (`f32 convert(bf16 convert(f32 x))`), and
    fusions whose called computation contains such a pair. These are
    exactly the instructions CPU XLA materialises when promoting a
    sub-f32 collective to its f32-only reduction runtime."""
    dtype_of: Dict[str, str] = {}
    converts: Dict[str, Tuple[str, str]] = {}
    comp_of: Dict[str, str] = {}
    fusions: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        if line and line[0] not in " \t":
            hm = _COMP_RE.match(line)
            if hm:
                comp = hm.group("name")
            continue
        dm = _DEF_RE.search(line)
        if dm:
            dtype_of[dm.group("name")] = dm.group("dtype")
        if " convert(" in line:
            cm = _CONVERT_RE.search(line)
            if cm:
                converts[cm.group("name")] = (cm.group("dst"), cm.group("op"))
                comp_of[cm.group("name")] = comp
        elif " fusion(" in line and "calls=" in line:
            fm = _FUSION_RE.search(line)
            if fm:
                fusions[fm.group("name")] = fm.group("comp")
    wire: Dict[str, str] = {}
    comp_wire: Dict[str, str] = {}
    for name, (dst, op) in converts.items():
        inner = converts.get(op)
        if inner is None:
            continue
        src = inner[0]  # the narrow type: the inner convert's result
        if (src not in _DTYPE_BYTES or dst not in _DTYPE_BYTES
                or _DTYPE_BYTES[src] >= _DTYPE_BYTES[dst]
                or dtype_of.get(inner[1]) != dst):
            continue
        wire[name] = src
        c = comp_of.get(name, "")
        if comp_wire.setdefault(c, src) != src:
            comp_wire[c] = "?"  # mixed wire dtypes: don't attribute
    for fname, cname in fusions.items():
        w = comp_wire.get(cname)
        if w and w != "?":
            wire[fname] = w
    return wire


def _wire_elements(line: str, m, wire: Dict[str, str]
                   ) -> List[Tuple[str, int]]:
    """One collective op (`m` = its `_OP_RE` match in `line`) → a
    `(wire dtype, payload bytes)` pair per array it carries. A combined
    collective has a tuple result whose i-th array is the reduction of
    its i-th operand; an operand that resolves through `_wire_dtypes` to
    a round-trip via a NARROWER dtype is charged at that source dtype.
    Whenever the pattern doesn't match — or the result arrays cannot be
    paired with the operands (async `-start` tuples) — the array keeps
    its own dtype: unscaled is the conservative (larger) count."""
    shapes = _SHAPE_RE.findall(m.group("shape"))
    om = _OPERAND_RE.search(line[m.end() - 1:])
    names = re.findall(r"%([\w.-]+)", om.group(1)) if om else []
    if len(names) != len(shapes):
        names = [""] * len(shapes)
    out: List[Tuple[str, int]] = []
    for (dtype, dims), name in zip(shapes, names):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        w = wire.get(name)
        if (w in _DTYPE_BYTES and dtype in _DTYPE_BYTES
                and _DTYPE_BYTES[w] < _DTYPE_BYTES[dtype]):
            dtype = w
        out.append((dtype, n * _DTYPE_BYTES.get(dtype, 0)))
    return out


_SUB_F32_WIRE = frozenset({"bf16", "f16", "f8e4m3fn", "f8e5m2"})


def collective_wire_dtypes(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per collective kind, counts of the arrays it carries by WIRE dtype:
    `{kind: {dtype: n}}` (a combined collective counts once per array of
    its tuple). The wire dtype is the array's own element type, except
    when its operand resolves through `_wire_dtypes`' promotion
    round-trip — then it is the SOURCE type the program requested (CPU
    XLA's f32-only reduction runtime materialises bf16 collectives as
    convert pairs; TPU runs them natively). This is the `dtype-wire`
    contract's HLO-tier input — the same `_wire_elements` accounting the
    inventory's payload bytes use, as a per-cell dtype table."""
    wire = _wire_dtypes(hlo_text)
    out: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        rec = out.setdefault(m.group("kind"), {})
        for dtype, _ in _wire_elements(line, m, wire):
            rec[dtype] = rec.get(dtype, 0) + 1
    return out


def audit_wire_dtypes(wire_table: Dict[str, Dict[str, int]],
                      declared: str, where: str) -> List[Finding]:
    """D5 at the compiled tier: every sub-f32 collective wire dtype must be
    DECLARED by the cell (`ShardedCase.wire_dtype`). The only shipped
    declaration is the `grad_reduce_dtype=bfloat16` round-trip; an
    undeclared narrow collective is an unreviewed precision cut on the
    gradient (or worse, activation) wire."""
    findings: List[Finding] = []
    for kind, dtypes in sorted(wire_table.items()):
        for dtype, count in sorted(dtypes.items()):
            if dtype in _SUB_F32_WIRE and dtype != declared:
                findings.append(Finding(
                    "dtype-wire", where,
                    f"{count} `{kind}` op(s) put {dtype} on the wire but "
                    f"the cell declares wire_dtype={declared} — the only "
                    "admitted sub-f32 collective is the declared "
                    "grad_reduce_dtype round-trip",
                    {"kind": kind, "dtype": dtype, "count": count,
                     "declared": declared}))
    return findings


def collective_inventory(hlo_text: str, mesh=None) -> Dict[str, Any]:
    """Aggregate the compiled program's collectives per kind:
    `{kinds: {kind: {count, bytes, max_op_bytes, axes: {axis: bytes}}},
    total_bytes}`. Bytes are per-device payload per step, summed over ops
    (how far the compiler combines the per-gradient all-reduces into
    tuple ops varies with the XLA build — the BYTES are the invariant).
    Axis attribution needs `mesh`; unattributable groups land on
    'unknown' (never silently dropped).

    Payloads are counted at the WIRE dtype the program requested: CPU
    XLA's reduction runtime is f32-only, so it rewrites every bf16
    collective as convert(bf16→f32) → collective(f32) → convert back —
    counting the f32 shape would erase exactly the payload halving a
    bf16 gradient reduction exists to buy (TPU runs the collective at
    bf16 natively). `_wire_elements` detects that promotion pattern per
    array of the op and charges it at its source dtype."""
    axis_parts = _axis_groupings(mesh) if mesh is not None else {}
    wire = _wire_dtypes(hlo_text)
    kinds: Dict[str, Dict[str, Any]] = {}
    total = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        payload = sum(b for _, b in _wire_elements(line, m, wire))
        groups = parse_replica_groups(line)
        axis = "unknown"
        if groups is not None:
            if not groups:
                # HLO shorthand: replica_groups={} = every device, one group
                axis = "all"
            elif all(len(g) <= 1 for g in groups):
                axis = "none"  # degenerate: no cross-device traffic
            else:
                for name, part in axis_parts.items():
                    if groups == part:
                        axis = name
                        break
        rec = kinds.setdefault(kind, {"count": 0, "bytes": 0,
                                      "max_op_bytes": 0, "axes": {}})
        rec["count"] += 1
        rec["bytes"] += payload
        rec["max_op_bytes"] = max(rec["max_op_bytes"], payload)
        rec["axes"][axis] = rec["axes"].get(axis, 0) + payload
        total += payload
    return {"kinds": kinds, "total_bytes": total}


def memory_budget(compiled) -> Dict[str, int]:
    """The executable's memory shape from `memory_analysis()`:
    argument/output/temp/alias bytes plus the derived peak
    (arg + out + temp − alias: donated-aliased buffers are counted once)."""
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    return {"arg_bytes": arg, "out_bytes": out, "temp_bytes": temp,
            "alias_bytes": alias,
            "peak_hbm_bytes": arg + out + temp - alias}


# ------------------------------------------------------- sharding table --

def _spec_str(sharding) -> str:
    spec = getattr(sharding, "spec", None)
    return str(spec) if spec is not None else str(sharding)


def _uses_axis(sharding, axis: str) -> bool:
    spec = getattr(sharding, "spec", None) or ()
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis in names:
            return True
    return False


def _local_leaf_bytes(leaf) -> int:
    """Per-device bytes of one arg leaf: the sharded LOCAL shard when the
    leaf (concrete array or annotated SDS) carries a NamedSharding, else
    the global shape."""
    shape = tuple(leaf.shape)
    sh = getattr(leaf, "sharding", None)
    if sh is not None and hasattr(sh, "shard_shape"):
        try:
            shape = sh.shard_shape(shape)
        except Exception:
            pass
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


def sharding_table(compiled, args: Sequence[Any]) -> List[Dict[str, Any]]:
    """One row per input leaf: `{path, shape, dtype, bytes, spec}` with
    `spec` read from the EXECUTABLE's input_shardings (what GSPMD settled
    on), `bytes` the leaf's global size. Row order is the args pytree's
    leaf order — identical between the two trees by construction."""
    flat_args = jax.tree_util.tree_flatten_with_path(tuple(args))[0]
    in_shardings = jax.tree_util.tree_leaves(
        compiled.input_shardings[0],
        is_leaf=lambda x: hasattr(x, "spec") or x is None)
    rows = []
    for (path, leaf), sh in zip(flat_args, in_shardings):
        rows.append({
            "path": jax.tree_util.keystr(path),
            "shape": tuple(leaf.shape),
            "dtype": str(np.dtype(leaf.dtype)),
            "bytes": int(np.prod(leaf.shape, dtype=np.int64))
            * np.dtype(leaf.dtype).itemsize,
            "spec": _spec_str(sh),
            "_sharding": sh,
        })
    return rows


def audit_sharding_table(rows: List[Dict[str, Any]], mesh, where: str,
                         replicated_threshold: int = REPLICATED_BYTES,
                         opt_state_threshold: Optional[int] = None
                         ) -> List[Finding]:
    """The ZeRO detector: a large input buffer replicated across a >1 data
    axis is state the data axis could shard. Now that ZeRO-1 has landed
    (train/steps.py), the train cells run this with a TIGHT
    `opt_state_threshold` on the optimizer-state rows (path contains
    'opt_state'), turning "unclaimed HBM win" into an ASSERTED property:
    any big momentum leaf left replicated across the data axis fails the
    analyzer. Both thresholds are per-case overridable
    (`ShardedCase.replicated_bytes` / `.opt_replicated_bytes`)."""
    from ..parallel.mesh import DATA_AXIS

    findings: List[Finding] = []
    if dict(mesh.shape).get(DATA_AXIS, 1) <= 1:
        return findings
    for row in rows:
        threshold = replicated_threshold
        what = "ZeRO-shardable state burning HBM on every data replica"
        if opt_state_threshold is not None and "opt_state" in row["path"]:
            threshold = opt_state_threshold
            what = ("optimizer state this cell asserts ZeRO-sharded "
                    "(parallel.zero_opt) — the partition silently "
                    "regressed to replicated")
        if (row["bytes"] >= threshold
                and not _uses_axis(row["_sharding"], DATA_AXIS)):
            findings.append(Finding(
                "sharding", where,
                f"{row['bytes']:,} B buffer `{row['path']}` "
                f"{row['shape']} is replicated across the "
                f"{dict(mesh.shape)[DATA_AXIS]}-way data axis "
                f"(spec {row['spec']}) — {what}",
                {"path": row["path"], "bytes": row["bytes"],
                 "spec": row["spec"]}))
    return findings


# ------------------------------------------------------- comms policies --

@dataclass(frozen=True)
class CommsPolicy:
    """What a program's compiled collectives are allowed to look like.

    `allowed_kinds` beyond which any op is a finding; `small_bytes` caps
    the PER-OP payload of allowed kinds (0 = uncapped — the train step's
    gradient all-reduces are as big as the gradients);
    `require_grad_allreduce` asserts the dp gradient set is PRESENT
    (data-axis gradient-reduction bytes ≥ the program's parameter bytes —
    the detector for a train step that silently stopped averaging); and
    `gather_bytes` (>0) caps the PER-OP all-gather payload for programs
    where weight-sized gathers are the DESIGN (ZeRO-1's parameter
    all-gather) — it supersedes the implicit-resharding detector with an
    explicit ceiling: one updated-param leaf per op, never a fused
    whole-model regather."""

    allowed_kinds: Tuple[str, ...]
    small_bytes: int = 0
    require_grad_allreduce: bool = False
    gather_bytes: int = 0


TRAIN_COMMS = CommsPolicy(allowed_kinds=("all-reduce",),
                          require_grad_allreduce=True)
# The ZeRO-1 train step (parallel.zero_opt): the gradient exchange may
# compile as all-reduce (CPU XLA keeps AR + per-shard slicing) or
# reduce-scatter (TPU), and the updated param shards all-gather back —
# per-op gathers bounded by the largest param leaf (9.4 MB conv kernel on
# the audit config; 10 MiB ceiling), so a whole-model regather still
# fails the cell. collective-permute is admitted because on COMPOSED
# meshes (dp×tp) GSPMD decomposes the params-replicated-over-both-axes
# gradient reduction into a half-payload data-axis all-reduce plus
# neighbor permutes that complete the exchange — same bytes, split across
# two op kinds (observed on the dp2tp2 cell).
ZERO_TRAIN_COMMS = CommsPolicy(
    allowed_kinds=("all-reduce", "reduce-scatter", "all-gather",
                   "collective-permute"),
    require_grad_allreduce=True,
    gather_bytes=10 * 1024 * 1024)
# eval/serve: "collective-free" up to control-sized payloads — the scalar
# metric reductions (all-reduce) and top-k's per-shard candidate exchange
# (all-gather, a few hundred bytes); the per-op cap is what keeps data and
# weights out, and the resharding detector independently catches
# weight-sized all-gathers
EVAL_COMMS = CommsPolicy(allowed_kinds=("all-reduce", "all-gather"),
                         small_bytes=SMALL_COLLECTIVE_BYTES)


def audit_collectives(inventory: Dict[str, Any], policy: CommsPolicy,
                      where: str, min_grad_bytes: int = 0,
                      data_axis_size: int = 1) -> List[Finding]:
    """Inventory × policy → findings: disallowed kinds, oversized ops in
    allowed kinds, a missing gradient all-reduce set, and (independent of
    policy) weight-sized all-gathers — the implicit-resharding detector.

    The gradient floor counts all-reduce bytes on data-spanning axes
    PLUS reduce-scatter bytes × `data_axis_size`: a reduce-scatter's
    result shape is 1/dp of the tensor it reduced, but it moves the same
    gradient information — without the scale-up, the ZeRO step on a TPU
    (where GSPMD emits genuine reduce-scatters) would trip the
    missing-gradient detector while reducing perfectly."""
    findings: List[Finding] = []
    kinds = inventory["kinds"]
    for kind, rec in sorted(kinds.items()):
        if kind not in policy.allowed_kinds:
            findings.append(Finding(
                "comms", where,
                f"`{kind}` in a program whose policy allows only "
                f"{list(policy.allowed_kinds)}: {rec['count']} op(s), "
                f"{rec['bytes']:,} B/step over axes "
                f"{sorted(rec['axes'])} — new cross-device traffic in "
                "the step",
                {"kind": kind, **{k: v for k, v in rec.items()}}))
        elif policy.small_bytes and rec["max_op_bytes"] > policy.small_bytes:
            findings.append(Finding(
                "comms", where,
                f"`{kind}` payload {rec['max_op_bytes']:,} B exceeds the "
                f"{policy.small_bytes:,} B scalar-reduction allowance for "
                "a collective-free program — this is data, not a metric "
                "sum (device-side eval accumulation ships counts only)",
                {"kind": kind, **{k: v for k, v in rec.items()}}))
    ag = kinds.get("all-gather")
    if ag and policy.gather_bytes:
        if ag["max_op_bytes"] > policy.gather_bytes:
            findings.append(Finding(
                "resharding", where,
                f"all-gather of {ag['max_op_bytes']:,} B exceeds this "
                f"program's {policy.gather_bytes:,} B per-op ceiling — "
                "bigger than any single param leaf, i.e. XLA fused a "
                "whole-model regather into the step instead of per-leaf "
                "ZeRO gathers",
                {k: v for k, v in ag.items()}))
    elif ag and ag["max_op_bytes"] >= RESHARD_BYTES:
        findings.append(Finding(
            "resharding", where,
            f"all-gather of {ag['max_op_bytes']:,} B inside the step — "
            "weight-sized, i.e. a parameter is implicitly resharded "
            "(gathered) every step instead of being laid out where it is "
            "consumed; pin it with in_shardings/with_sharding_constraint",
            {k: v for k, v in ag.items()}))
    if policy.require_grad_allreduce and min_grad_bytes > 0:
        got = sum(b for label, b in
                  kinds.get("all-reduce", {}).get("axes", {}).items()
                  if _spans_data(label))
        got += data_axis_size * sum(
            b for label, b in
            kinds.get("reduce-scatter", {}).get("axes", {}).items()
            if _spans_data(label))
        if "collective-permute" in policy.allowed_kinds:
            # On composed meshes GSPMD lowers part of the gradient
            # exchange to collective-permutes (see ZERO_TRAIN_COMMS);
            # permutes carry source_target_pairs, not replica_groups, so
            # their bytes are axis-unattributable and count toward the
            # floor only under a policy that explicitly admits the kind.
            got += kinds.get("collective-permute", {}).get("bytes", 0)
        if got < min_grad_bytes:
            findings.append(Finding(
                "comms", where,
                f"gradient reductions spanning the data axis carry "
                f"{got:,} B/step "
                f"but the program requires {min_grad_bytes:,} B — the "
                "gradient all-reduce set is missing or truncated (replicas "
                "are silently training on local gradients)",
                {"data_axis_allreduce_bytes": got,
                 "param_bytes": min_grad_bytes}))
    return findings


# ------------------------------------------------- compile + evidence --

def _unaliased_from_warnings(caught) -> List[Dict[str, Any]]:
    from .jaxpr_audit import _shape_bytes

    unaliased: List[Dict[str, Any]] = []
    for w in caught:
        msg = str(w.message)
        if "donated" not in msg.lower():
            continue
        for shape in re.findall(r"[a-z0-9]+\[[\d,]*\](?:\{[\d,]*\})?", msg):
            unaliased.append({"buffer": shape.split("{")[0],
                              "bytes": _shape_bytes(shape)})
    return unaliased


def _compile_with_evidence(jitted_fn, args: Sequence[Any],
                           donated_argnums: Sequence[int] = (),
                           mesh=None) -> Tuple[Dict[str, Any], Any]:
    """ONE AOT lower+compile yielding (evidence, compiled). Evidence
    carries the donation fields (donated bytes are per-device LOCAL under
    a sharded mesh — `shard_shape` — matching the per-device alias table
    memory_analysis reports), the collective inventory, and the memory
    budget — the superset `step_comms_evidence` and the sharded audit both
    ride, so neither pays a second compile."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jitted_fn.lower(*args).compile()
    budget = memory_budget(compiled)
    inventory = collective_inventory(compiled.as_text(), mesh)
    donated = sum(_local_leaf_bytes(l) for i in donated_argnums
                  for l in jax.tree_util.tree_leaves(args[i]))
    coverage = (round(budget["alias_bytes"] / donated, 4)
                if donated else None)
    ev = {
        "donated_bytes": donated,
        "aliased_bytes": budget["alias_bytes"] if donated else None,
        "donation_coverage": coverage,
        "temp_bytes": budget["temp_bytes"],
        "unaliased": _unaliased_from_warnings(caught) if donated else [],
        "collective_bytes_per_step": inventory["total_bytes"],
        "peak_hbm_bytes": budget["peak_hbm_bytes"],
        "collectives": inventory,
        "memory": budget,
    }
    return ev, compiled


def step_comms_evidence(jitted_fn, args: Sequence[Any],
                        donated_argnums: Sequence[int] = (0,),
                        mesh=None) -> Dict[str, Any]:
    """The evidence of one jitted program without a `ShardedCase` around it
    (called by tests/test_analysis.py only; the audit matrix calls
    `_compile_with_evidence` itself — ROADMAP D7): the donation fields
    (jaxpr_audit.donation_evidence-compatible) plus
    `collective_bytes_per_step` and `peak_hbm_bytes`, from a single
    compile."""
    ev, _ = _compile_with_evidence(jitted_fn, args, donated_argnums, mesh)
    return ev


# ------------------------------------------------------ the audit matrix --

@dataclass
class ShardedCase:
    """One (program, mesh) cell of the sharded audit matrix.

    `replicated_bytes` / `opt_replicated_bytes` override the
    `audit_sharding_table` thresholds per cell (None = module defaults):
    the ZeRO train cells run the optimizer-state rows at 1 MiB so the
    asserted-sharded property is non-vacuous on the tiny audit config
    (largest momentum leaf 9.4 MB — far under the 16 MiB general
    threshold). `min_grad_fraction` scales the gradient-reduction floor:
    the bf16-wire cell legitimately ships HALF the f32 gradient bytes.
    `wire_dtype` is the narrowest collective element type the cell
    DECLARES ('bf16' only on the grad_reduce_dtype=bfloat16 cell): any
    sub-f32 wire dtype beyond it is a `dtype-wire` finding (D5)."""

    name: str          # registry program name
    mesh_name: str     # composed_audit_meshes key: 'dp2' | 'dp2tp2' | 'dp4'
    build: Callable[[AuditContext, Any], Tuple[Any, Tuple[Any, ...]]]
    policy: CommsPolicy
    donate: Tuple[int, ...] = ()
    replicated_bytes: Optional[int] = None
    opt_replicated_bytes: Optional[int] = None
    min_grad_fraction: float = 1.0
    wire_dtype: str = "f32"

    @property
    def key(self) -> str:
        return f"{self.name}@{self.mesh_name}"


# the ZeRO cells' asserted-property threshold for optimizer-state rows
ZERO_OPT_REPLICATED_BYTES = 1024 * 1024


def _case_train(ctx: AuditContext, mesh):
    from ..train.steps import make_train_step

    cfg, model, tx, state = ctx.state_for("baseline")
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _case_train_replicated(ctx: AuditContext, mesh):
    """The pre-ZeRO anchor: zero_opt forced off, so the committed baseline
    keeps the replicated-optimizer program's payload/peak-HBM next to the
    ZeRO cells — the delta IS the evidence (`--diff-baseline` fails if
    either side drifts)."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.zero_opt = "off"
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh, zero_opt="off"),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _case_train_bf16(ctx: AuditContext, mesh):
    """The bf16-wire gradient reduction, zero_opt off so the cell isolates
    ONE effect: the reduction payload halves against the replicated
    anchor while peak HBM stays in family."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.zero_opt = "off"
    cfg.parallel.grad_reduce_dtype = "bfloat16"
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh, zero_opt="off"),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _case_train_accum(ctx: AuditContext, mesh):
    """K=4 gradient accumulation over ZeRO-1 (`parallel.grad_accum`,
    steps.py `_accum_grad_section`): the batch scans as 4 microbatches
    inside the step and the data-axis gradient reduction runs ONCE per
    optimizer step, OUTSIDE the scan's while body — so the banked payload
    equals the K=1 anchor's while amortizing over 4× the samples-per-
    reduction. The audit batch is 8 → per-replica 4 → microbatch 1 on
    the 2-way data axis."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.grad_accum = 4
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _case_train_accum_bf16(ctx: AuditContext, mesh):
    """The compound lever: K=4 accumulation × bf16 wire — ONE deferred
    reduction per optimizer step at HALF the f32 payload (÷2K
    per-microbatch bytes vs the K=1 f32 anchor). zero_opt off to mirror
    `_case_train_bf16`, isolating the wire effect."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.zero_opt = "off"
    cfg.parallel.grad_reduce_dtype = "bfloat16"
    cfg.parallel.grad_accum = 4
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh, zero_opt="off"),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def _case_eval(ctx: AuditContext, mesh):
    from ..train.steps import make_eval_step

    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_eval_step(cfg, model, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh),
                batch_sharded(ctx.valid(), mesh))


def _case_nested_eval(ctx: AuditContext, mesh):
    from ..train.steps import make_nested_eval_step

    cfg, model, _, state = ctx.state_for("nested")
    fn = make_nested_eval_step(cfg, model)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh),
                batch_sharded(ctx.valid(), mesh))


def _case_plc_predict(ctx: AuditContext, mesh):
    from ..train.steps import make_predict_step

    cfg, model, _, state = ctx.state_for("baseline")
    return make_predict_step(cfg, model), (
        abstract_state(state, mesh), batch_sharded(ctx.images(), mesh))


def _case_topk_predict(ctx: AuditContext, mesh):
    from ..train.steps import make_topk_predict_step

    cfg, model, _, state = ctx.state_for("baseline")
    return make_topk_predict_step(cfg, model, k=3), (
        abstract_state(state, mesh), batch_sharded(ctx.images(), mesh))


def _case_topk_predict_serve(ctx: AuditContext, mesh):
    """The serve engine's dp-sharded predict (serve/engine.py on a mesh):
    make_topk_predict_step built WITH mesh= so the (B, k) outputs are
    pinned batch-sharded — the program every serving replica actually
    runs, banked under the serve CommsPolicy (EVAL_COMMS: top-k candidate
    exchanges only, control-sized)."""
    from ..train.steps import make_topk_predict_step

    cfg, model, _, state = ctx.state_for("baseline")
    return make_topk_predict_step(cfg, model, k=3, mesh=mesh), (
        abstract_state(state, mesh), batch_sharded(ctx.images(), mesh))


def sharded_registry() -> List[ShardedCase]:
    """The audited (program, mesh) matrix. Train + the serve hot path
    (topk) and eval run on BOTH composed meshes; the remaining eval-family
    programs on the composed dp×tp mesh (their dp-only structure is the
    dp2 eval cell's, minus the class-dim split). Ordered cheap-first so a
    red CLI run fails fast; each cell is one lower+compile."""
    return [
        ShardedCase("plc_predict", "dp2tp2", _case_plc_predict, EVAL_COMMS),
        ShardedCase("topk_predict", "dp2", _case_topk_predict, EVAL_COMMS),
        ShardedCase("topk_predict", "dp2tp2", _case_topk_predict, EVAL_COMMS),
        # the serve engine's dp-sharded predict (output layout pinned):
        # the program behind `--serve_devices`, proven control-plane-cheap
        ShardedCase("topk_predict_serve_dp", "dp2",
                    _case_topk_predict_serve, EVAL_COMMS),
        ShardedCase("topk_predict_serve_dp_tp", "dp2tp2",
                    _case_topk_predict_serve, EVAL_COMMS),
        # the serve-FLEET cell: the same serve program at the dp4 width an
        # autoscaled replica provisions — banked so --diff-baseline fences
        # the fleet hot path's comms/HBM at its own data-axis width
        ShardedCase("topk_predict_serve_fleet", "dp4",
                    _case_topk_predict_serve, EVAL_COMMS),
        ShardedCase("eval_step", "dp2", _case_eval, EVAL_COMMS),
        ShardedCase("eval_step", "dp2tp2", _case_eval, EVAL_COMMS),
        ShardedCase("nested_eval_step", "dp2tp2", _case_nested_eval,
                    EVAL_COMMS),
        # ZeRO-1 cells (parallel.zero_opt default auto=on): optimizer
        # rows ASSERTED data-sharded at the tight threshold
        ShardedCase("train_step", "dp2", _case_train, ZERO_TRAIN_COMMS,
                    donate=(0,),
                    opt_replicated_bytes=ZERO_OPT_REPLICATED_BYTES),
        ShardedCase("train_step", "dp2tp2", _case_train, ZERO_TRAIN_COMMS,
                    donate=(0,),
                    opt_replicated_bytes=ZERO_OPT_REPLICATED_BYTES),
        # the pre-ZeRO anchor and the bf16-wire variant: both banked so
        # --diff-baseline pins the payload/HBM deltas as committed evidence
        ShardedCase("train_step_replicated", "dp2", _case_train_replicated,
                    TRAIN_COMMS, donate=(0,)),
        ShardedCase("train_step_bf16", "dp2", _case_train_bf16,
                    TRAIN_COMMS, donate=(0,), min_grad_fraction=0.5,
                    wire_dtype="bf16"),
        # K-step accumulation cells (parallel.grad_accum=4): the banked
        # property is ONE data-axis gradient reduction per OPTIMIZER step
        # with the K=1 anchor's payload (per-microbatch bytes ÷K), checked
        # against the anchors by tests/test_zero_opt.py
        ShardedCase("train_step_accum4", "dp2", _case_train_accum,
                    ZERO_TRAIN_COMMS, donate=(0,),
                    opt_replicated_bytes=ZERO_OPT_REPLICATED_BYTES),
        ShardedCase("train_step_accum4", "dp2tp2", _case_train_accum,
                    ZERO_TRAIN_COMMS, donate=(0,),
                    opt_replicated_bytes=ZERO_OPT_REPLICATED_BYTES),
        ShardedCase("train_step_accum4_bf16", "dp2",
                    _case_train_accum_bf16, TRAIN_COMMS, donate=(0,),
                    min_grad_fraction=0.5, wire_dtype="bf16"),
    ]


def _param_bytes(ctx: AuditContext, workload: str = "baseline") -> int:
    _, _, _, state = ctx.state_for(workload)
    return sum(int(np.prod(l.shape, dtype=np.int64))
               * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(state.params))


def audit_sharded_case(case: ShardedCase, ctx: AuditContext
                       ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Compile one matrix cell and run every detector over it; returns
    (findings, the baseline record for analysis/baselines.json)."""
    from ..parallel.mesh import DATA_AXIS

    mesh = ctx.composed_mesh(case.mesh_name)
    fn, args = case.build(ctx, mesh)
    ev, compiled = _compile_with_evidence(fn, args, case.donate, mesh)
    where = case.key

    findings = audit_collectives(
        ev["collectives"], case.policy, where,
        min_grad_bytes=int(_param_bytes(ctx) * case.min_grad_fraction) if
        case.policy.require_grad_allreduce else 0,
        data_axis_size=dict(mesh.shape).get(DATA_AXIS, 1))

    # D5 at the compiled tier: the cell's collective wire-dtype table is a
    # CONTRACT (and a banked baseline key), not just payload accounting
    wire_table = collective_wire_dtypes(compiled.as_text())
    findings += audit_wire_dtypes(wire_table, case.wire_dtype, where)

    rows = sharding_table(compiled, args)
    findings += audit_sharding_table(
        rows, mesh, where,
        replicated_threshold=(REPLICATED_BYTES if case.replicated_bytes
                              is None else case.replicated_bytes),
        opt_state_threshold=case.opt_replicated_bytes)

    if case.donate:
        if ev["unaliased"] or (ev["donation_coverage"] is not None
                               and ev["donation_coverage"] < 1.0):
            per_buf = ", ".join(f"{u['buffer']}={u['bytes']}B"
                                for u in ev["unaliased"]) or "n/a"
            findings.append(Finding(
                "donation", where,
                f"donated inputs not fully aliased on this mesh: "
                f"{ev['aliased_bytes']} of {ev['donated_bytes']} local "
                f"bytes aliased (coverage {ev['donation_coverage']}); "
                f"unaliased buffers: {per_buf}",
                {k: ev[k] for k in ("donated_bytes", "aliased_bytes",
                                    "donation_coverage", "unaliased")}))

    record = {
        "mesh": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        "collectives": {
            kind: {"count": rec["count"], "bytes": rec["bytes"],
                   "max_op_bytes": rec["max_op_bytes"],
                   "axes": dict(sorted(rec["axes"].items()))}
            for kind, rec in sorted(ev["collectives"]["kinds"].items())},
        "collective_bytes_per_step": ev["collective_bytes_per_step"],
        "wire_dtypes": {k: dict(sorted(v.items()))
                        for k, v in sorted(wire_table.items())},
        "peak_hbm_bytes": ev["peak_hbm_bytes"],
        "temp_bytes": ev["memory"]["temp_bytes"],
        "arg_bytes": ev["memory"]["arg_bytes"],
        "out_bytes": ev["memory"]["out_bytes"],
        "donation_coverage": ev["donation_coverage"],
        # the non-replicated input leaves: the baseline's sharding digest —
        # a leaf leaving this dict (or weakening its spec) is a downgrade
        "sharded_leaves": {
            r["path"]: r["spec"] for r in rows
            if getattr(r["_sharding"], "spec", None)
            and any(e is not None for e in r["_sharding"].spec)},
        "n_input_leaves": len(rows),
    }
    return findings, record


def audit_sharded_registry(ctx: Optional[AuditContext] = None,
                           cases: Optional[List[ShardedCase]] = None
                           ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit every matrix cell; returns (findings, {program@mesh: record})
    — the records feed `analysis/baseline.py`."""
    ctx = ctx or AuditContext()
    records: Dict[str, Any] = {}
    findings: List[Finding] = []
    for case in (cases if cases is not None else sharded_registry()):
        f, rec = audit_sharded_case(case, ctx)
        findings += f
        records[case.key] = rec
    return findings, records
