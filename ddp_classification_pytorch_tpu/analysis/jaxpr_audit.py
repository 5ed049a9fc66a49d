"""Jaxpr/HLO audit of every jitted step factory.

The registry below names each hot-path program the framework runs (train,
eval, nested-eval, PLC-predict, top-k serve predict) together with the
invariants its factory promises. The audit lowers each to a jaxpr (and,
where donation is promised, all the way to a compiled executable) on
synthetic avals of a tiny config and checks the *program*, not the source
text:

- **donation** — inputs declared donated must actually be aliased in the
  executable's `input_output_alias` table. An unaliased donated buffer means
  a state leaf round-trips HBM every step; the finding reports the per-buffer
  byte counts from XLA's own "donated buffers were not usable" diagnostic and
  the aliased/donated byte totals from `Compiled.memory_analysis()`.
- **callback** — hot-path programs must contain no
  `pure_callback`/`io_callback`/`debug_callback` primitives (each is a host
  round-trip inside the step).
- **uint8-epilogue** — every uint8 input aval must reach the model only
  through the `device_input_epilogue` pattern (`convert_element_type` →
  `div 255`), i.e. raw pixels are normalized in-jit, never fed to a conv.
- **collectives** — eval/serve programs must carry no jaxpr-level collective
  primitives: a collective in a program some hosts skip (eval_every, serve)
  is exactly the desync that hangs a pod's control collectives
  (parallel/fleet.py). Train-path entries that legitimately use collectives
  (the train step's shard_map sections) opt out via `allow_collectives`.

Entries trace/compile in a fraction of the real model's cost (resnet18,
32 px, batch 8) — invariants are shape/dtype/program-structure properties,
independent of model scale.
"""

from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np

from . import Finding

# host-callback primitives: each one is a device→host→device round trip
# inside the program — fatal to an async-dispatch hot path
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
})

# jaxpr-level collective primitives (shard_map/pmap world). XLA-inserted
# collectives from auto-sharding don't appear here — those are exactly the
# per-step data collectives every host runs; what this detects is a program
# EXPLICITLY requesting cross-host exchange where the fleet design says the
# program must be host-local.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
})

# eqn params that hold sub-jaxprs under these keys
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                  "branches", "jaxprs")


def _sub_jaxprs(eqn) -> List[Any]:
    """Every inner jaxpr of an eqn (jit, scan, cond, shard_map, remat, …)."""
    subs: List[Any] = []
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(x, "jaxpr", x if hasattr(x, "eqns") else None)
            if j is not None and hasattr(j, "eqns"):
                subs.append(j)
    return subs


def collect_primitives(jaxpr) -> set:
    """All primitive names in a jaxpr, recursing into sub-jaxprs."""
    prims: set = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            prims.add(eqn.primitive.name)
            stack.extend(_sub_jaxprs(eqn))
    return prims


# ------------------------------------------------------------ uint8 pass --

# primitives allowed to carry a uint8 input INTO a sub-jaxpr unchanged
_PASSTHROUGH = frozenset({
    "jit", "closed_call", "core_call", "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
})


def _is_var(v) -> bool:
    return not isinstance(v, Literal)


def _div_by_255(jaxpr, var) -> bool:
    """Is `var` consumed by the epilogue's `x / 255.0` (or `x * (1/255)`)?"""
    for eqn in jaxpr.eqns:
        if not any(u is var for u in eqn.invars if _is_var(u)):
            continue
        for other in eqn.invars:
            if isinstance(other, Literal):
                try:
                    val = float(np.asarray(other.val))
                except (TypeError, ValueError):
                    continue
                if eqn.primitive.name == "div" and val == 255.0:
                    return True
                if (eqn.primitive.name == "mul"
                        and abs(val - 1.0 / 255.0) < 1e-12):
                    return True
    return False


def audit_uint8_epilogue(closed_jaxpr, where: str) -> List[Finding]:
    """Every uint8 input of the program must flow ONLY into
    `convert_element_type` eqns whose output is immediately divided by 255
    (the `device_input_epilogue` normalize) — a uint8 aval consumed by
    anything else (or converted without the /255) is raw-pixel data
    reaching the model un-normalized."""
    findings: List[Finding] = []
    jaxpr = closed_jaxpr.jaxpr

    def check_var(j, var):
        for eqn in j.eqns:
            positions = [i for i, u in enumerate(eqn.invars)
                         if _is_var(u) and u is var]
            if not positions:
                continue
            name = eqn.primitive.name
            if name == "convert_element_type":
                out = eqn.outvars[0]
                if not _div_by_255(j, out):
                    findings.append(Finding(
                        "uint8-epilogue", where,
                        "uint8 input converted to float without the /255 "
                        "normalize — raw pixel values reach the model "
                        "(device_input_epilogue bypassed)",
                        {"primitive": name}))
            elif name in _PASSTHROUGH:
                for sub in _sub_jaxprs(eqn):
                    for i in positions:
                        if i < len(sub.invars):
                            check_var(sub, sub.invars[i])
            else:
                findings.append(Finding(
                    "uint8-epilogue", where,
                    f"uint8 input consumed by `{name}` instead of the "
                    "normalize epilogue (device_input_epilogue bypassed)",
                    {"primitive": name}))

    for var in jaxpr.invars:
        if getattr(var.aval, "dtype", None) == jnp.uint8:
            check_var(jaxpr, var)
    return findings


# --------------------------------------------------------- donation pass --

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(shape_str: str) -> Optional[int]:
    """Bytes of an HLO shape literal like `f32[16,32,32,3]{3,2,1,0}`."""
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", shape_str.strip())
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[m.group(1)]


def _leaf_bytes(leaf) -> int:
    """Per-device bytes of one donated leaf: the LOCAL shard when the leaf
    carries a sharding, else the global shape. XLA's alias table
    (`alias_size_in_bytes`) is per-device, so a ZeRO-sharded momentum
    leaf donates 1/dp of its global bytes on each device — counting the
    global size would report coverage < 1.0 on a fully aliased step."""
    shape = tuple(leaf.shape)
    sh = getattr(leaf, "sharding", None)
    if sh is not None and hasattr(sh, "shard_shape"):
        try:
            shape = sh.shard_shape(shape)
        except Exception:
            pass
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


def donation_evidence(jitted_fn, args: Sequence[Any],
                      donated_argnums: Sequence[int] = (0,)) -> Dict[str, Any]:
    """Donation/memory evidence for one jitted program at these args' avals:
    `{donated_bytes, aliased_bytes, donation_coverage, temp_bytes,
    unaliased}` — `unaliased` lists the per-buffer shapes+bytes XLA reported
    as donated-but-not-usable (each one is a buffer round-tripping HBM).

    AOT `lower().compile()` does not populate the jit call cache, so this
    costs one compile (`audit_donation` is the caller; a persistent-cache
    hit on the TPU)."""
    donated = sum(_leaf_bytes(l) for i in donated_argnums
                  for l in jax.tree_util.tree_leaves(args[i]))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jitted_fn.lower(*args).compile()
    unaliased: List[Dict[str, Any]] = []
    for w in caught:
        msg = str(w.message)
        if "donated" not in msg.lower():
            continue
        for shape in re.findall(r"[a-z0-9]+\[[\d,]*\](?:\{[\d,]*\})?", msg):
            unaliased.append({"buffer": shape.split("{")[0],
                              "bytes": _shape_bytes(shape)})
    aliased = None
    temp = None
    try:
        ma = compiled.memory_analysis()
        aliased = int(ma.alias_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
    except Exception:
        # runtimes without memory_analysis: fall back to counting the alias
        # table entries' param bytes out of the HLO header
        head = compiled.as_text().splitlines()[0]
        m = re.search(r"entry_computation_layout=\{\((.*?)\)->", head)
        if m:
            sizes = [_shape_bytes(s) or 0
                     for s in re.findall(r"[a-z0-9]+\[[\d,]*\]\{[\d,]*\}",
                                         m.group(1))]
            idx = {int(i) for i in re.findall(r"\((\d+), \{\}", head)}
            aliased = sum(sizes[i] for i in idx if i < len(sizes))
    coverage = (aliased / donated) if (aliased is not None and donated) else None
    return {
        "donated_bytes": donated,
        "aliased_bytes": aliased,
        "donation_coverage": round(coverage, 4) if coverage is not None else None,
        "temp_bytes": temp,
        "unaliased": unaliased,
    }


def audit_donation(jitted_fn, args: Sequence[Any], where: str,
                   donated_argnums: Sequence[int] = (0,)
                   ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Findings when declared-donated inputs are not fully aliased in the
    compiled executable (each gap is a buffer round-tripping HBM every
    step), plus the evidence dict either way."""
    ev = donation_evidence(jitted_fn, args, donated_argnums)
    findings: List[Finding] = []
    aliased = ev["aliased_bytes"]
    if ev["unaliased"] or (aliased is not None
                           and aliased < ev["donated_bytes"]):
        gap = (ev["donated_bytes"] - aliased) if aliased is not None else None
        per_buf = ", ".join(
            f"{u['buffer']}={u['bytes']}B" for u in ev["unaliased"]) or "n/a"
        findings.append(Finding(
            "donation", where,
            f"donated inputs not fully aliased: {aliased} of "
            f"{ev['donated_bytes']} bytes aliased"
            + (f" ({gap} bytes round-trip HBM every step)" if gap else "")
            + f"; unaliased buffers: {per_buf}",
            ev))
    return findings, ev


# ---------------------------------------------------------------- registry --

@dataclass
class StepSpec:
    """One registered jitted step factory and the invariants it promises.

    `factory` is `module:function` provenance — the lint pass scans exactly
    these functions for host-sync idioms, so the two passes cannot drift
    apart. `donate` names argnums that MUST be donated and fully aliased;
    an empty `donate` requires `no_donate_reason` (the documented why —
    see docs/analysis.md invariant catalogue)."""

    name: str
    factory: str
    build: Callable[["AuditContext"], Tuple[Any, Tuple[Any, ...]]]
    donate: Tuple[int, ...] = ()
    no_donate_reason: str = ""
    hot_path: bool = True
    allow_collectives: bool = False
    uint8_input: bool = False
    evidence: Dict[str, Any] = dc_field(default_factory=dict)


# the reason the non-train steps do NOT donate, verified by the audit's
# construction (state reused call-to-call) — mirrored in train/steps.py
_EVAL_NO_DONATE = (
    "state is live across calls (the same TrainState feeds every val/serve "
    "batch; donating it would delete the buffers after the first batch), "
    "and the dead per-batch inputs (uint8 images, i32 labels) have no "
    "same-shape/dtype outputs to alias — donating them would only produce "
    "XLA 'donation not used' stalls, not reuse"
)


class AuditContext:
    """Tiny-config model/state cache shared by every registry entry.

    One resnet18/cifar-stem f32 state for the fc-head entries, one for the
    nested head — built lazily so `--passes lint` never touches the
    backend, and cached so the test suite's module-scoped audit pays each
    init exactly once."""

    def __init__(self, arch: str = "resnet18", image_size: int = 32,
                 num_classes: int = 8, batch: int = 8):
        self.arch, self.image_size = arch, image_size
        self.num_classes, self.batch = num_classes, batch
        self._cache: Dict[str, Any] = {}

    def tiny_cfg(self, workload: str = "baseline"):
        from ..config import get_preset

        cfg = get_preset(workload)
        cfg.data.dataset = "synthetic"
        cfg.data.image_size = self.image_size
        cfg.data.num_classes = self.num_classes
        cfg.data.batch_size = self.batch
        cfg.model.arch = self.arch
        cfg.model.variant = "cifar"
        cfg.model.dtype = "float32"
        cfg.optim.warmup_iters = 0
        return cfg

    @property
    def mesh(self):
        if "mesh" not in self._cache:
            from ..parallel import mesh as meshlib

            self._cache["mesh"] = meshlib.make_mesh()
        return self._cache["mesh"]

    def composed_mesh(self, name: str):
        """One of the composed audit meshes ('dp2' 2×1, 'dp2tp2' 2×2) from
        `parallel.mesh.composed_audit_meshes`, cached. Raises with the fix
        spelled out when the host exposes too few devices — the CLI
        self-forces 8 virtual CPU devices for exactly this reason."""
        key = f"mesh:{name}"
        if key not in self._cache:
            from ..parallel import mesh as meshlib

            meshes = meshlib.composed_audit_meshes()
            if name not in meshes:
                raise RuntimeError(
                    f"composed audit mesh '{name}' needs more devices than "
                    f"the {jax.device_count()} visible — force a multi-device "
                    "CPU backend (XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=8, set automatically by cli.analyze)")
            self._cache[key] = meshes[name]
        return self._cache[key]

    def state_for(self, workload: str):
        """(cfg, model, tx, state) for a workload preset, cached."""
        if workload not in self._cache:
            from ..train.state import create_train_state

            cfg = self.tiny_cfg(workload)
            model, tx, state = create_train_state(cfg, self.mesh,
                                                  steps_per_epoch=4)
            self._cache[workload] = (cfg, model, tx, state)
        return self._cache[workload]

    # synthetic avals of the H2D wire
    def images(self, dtype=jnp.uint8):
        h = self.image_size
        return jax.ShapeDtypeStruct((self.batch, h, h, 3), dtype)

    def labels(self):
        return jax.ShapeDtypeStruct((self.batch,), jnp.int32)

    def valid(self):
        return jax.ShapeDtypeStruct((self.batch,), jnp.float32)


def abstract_state(state, mesh, zero_opt: str = "auto"):
    """Re-home a concrete TrainState onto `mesh` as ShapeDtypeStructs
    carrying that mesh's DECLARED shardings (params/opt under
    `parallel.mesh`'s rules — so a >1 'model' axis actually class-shards
    the head — batch_stats and step replicated, matching
    train/state.py::create_train_state). `zero_opt` follows the
    `parallel.zero_opt` setting: the default 'auto' ZeRO-shards the big
    optimizer leaves over 'data' whenever the mesh's data axis spans
    devices — keep it in lockstep with the audited step's config, or the
    compile pays resharding collectives the real trainer never sees.
    Abstract avals are enough for both `jax.make_jaxpr` and AOT
    `lower().compile()`, so one cached state init serves every audited
    mesh without per-mesh init compiles."""
    from ..parallel import mesh as meshlib
    from ..train.state import state_shardings

    shardings = state_shardings(
        state, mesh, meshlib.zero_opt_enabled(zero_opt, mesh))
    return jax.tree_util.tree_map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh),
        state, shardings)


def batch_sharded(sds, mesh):
    """A batch-input aval re-annotated with `mesh`'s leading-axis (data)
    sharding — how the loader's global arrays actually arrive."""
    from ..parallel.mesh import batch_sharding

    return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                sharding=batch_sharding(mesh))


def _build_train(ctx: AuditContext):
    from ..train.steps import make_train_step

    cfg, model, tx, state = ctx.state_for("baseline")
    fn = make_train_step(cfg, model, tx, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels())


def _build_eval(ctx: AuditContext):
    from ..train.steps import make_eval_step

    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_eval_step(cfg, model, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels(), ctx.valid())


def _build_nested_eval(ctx: AuditContext):
    from ..train.steps import make_nested_eval_step

    cfg, model, _, state = ctx.state_for("nested")
    fn = make_nested_eval_step(cfg, model)
    return fn, (state, ctx.images(), ctx.labels(), ctx.valid())


def _build_plc_predict(ctx: AuditContext):
    from ..train.steps import make_predict_step

    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_predict_step(cfg, model)
    return fn, (state, ctx.images())


def _build_topk_predict(ctx: AuditContext):
    from ..train.steps import make_topk_predict_step

    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_topk_predict_step(cfg, model, k=3)
    return fn, (state, ctx.images())


def _build_train_survivor(ctx: AuditContext):
    """The re-formed-pod program: after elastic membership shrinks the
    world (parallel/fleet.py), the trainer rebuilds the SAME step
    factory on a mesh resolved for the survivor device count — a
    different jaxpr (no cross-device collectives at world 1), so it
    gets its own audit entry per the registry NOTE."""
    from ..parallel import mesh as meshlib
    from ..train.state import create_train_state
    from ..train.steps import make_train_step

    if "survivor" not in ctx._cache:
        mesh = meshlib.make_mesh(devices=jax.devices()[:1])
        cfg = ctx.tiny_cfg("baseline")
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=4)
        ctx._cache["survivor"] = (cfg, model, tx, state, mesh)
    cfg, model, tx, state, mesh = ctx._cache["survivor"]
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (state, ctx.images(), ctx.labels())


# --- composed dp×tp builds (registry NOTE, PR 6): the same eval/serve
# factories, but with state re-homed onto the 2×2 dp×tp audit mesh and
# batch inputs data-sharded — so the SHARDED variants of these programs
# (class-dim-split head, sharded batch) are donation/epilogue/collective-
# audited too, not only the 1-device audit build. Trace-only entries
# (no donate → no compile), so each costs one make_jaxpr.

def _dp_tp_args(ctx: AuditContext, workload: str, *, labels: bool,
                valid: bool):
    mesh = ctx.composed_mesh("dp2tp2")
    _, _, _, state = ctx.state_for(workload)
    args = [abstract_state(state, mesh), batch_sharded(ctx.images(), mesh)]
    if labels:
        args.append(batch_sharded(ctx.labels(), mesh))
    if valid:
        args.append(batch_sharded(ctx.valid(), mesh))
    return mesh, tuple(args)


def _build_eval_dp_tp(ctx: AuditContext):
    from ..train.steps import make_eval_step

    cfg, model, _, _ = ctx.state_for("baseline")
    mesh, args = _dp_tp_args(ctx, "baseline", labels=True, valid=True)
    return make_eval_step(cfg, model, mesh=mesh), args


def _build_nested_eval_dp_tp(ctx: AuditContext):
    from ..train.steps import make_nested_eval_step

    cfg, model, _, _ = ctx.state_for("nested")
    _, args = _dp_tp_args(ctx, "nested", labels=True, valid=True)
    return make_nested_eval_step(cfg, model), args


def _build_plc_predict_dp_tp(ctx: AuditContext):
    from ..train.steps import make_predict_step

    cfg, model, _, _ = ctx.state_for("baseline")
    _, args = _dp_tp_args(ctx, "baseline", labels=False, valid=False)
    return make_predict_step(cfg, model), args


def _build_topk_predict_dp_tp(ctx: AuditContext):
    from ..train.steps import make_topk_predict_step

    cfg, model, _, _ = ctx.state_for("baseline")
    _, args = _dp_tp_args(ctx, "baseline", labels=False, valid=False)
    return make_topk_predict_step(cfg, model, k=3), args


def _build_topk_predict_serve_dp(ctx: AuditContext):
    """The dp-sharded SERVE predict (serve/engine.py on a mesh): same
    forward as topk_predict but built with mesh= so the (B, k) outputs
    are pinned batch-sharded over 'data' — a distinct program (explicit
    output layout, dp-split top-k) that carries the serve-path throughput
    claim, so it gets its own audit entry per the registry NOTE."""
    from ..train.steps import make_topk_predict_step

    mesh = ctx.composed_mesh("dp2")
    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_topk_predict_step(cfg, model, k=3, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh))


def _build_topk_predict_serve_dp_tp(ctx: AuditContext):
    from ..train.steps import make_topk_predict_step

    cfg, model, _, _ = ctx.state_for("baseline")
    mesh, args = _dp_tp_args(ctx, "baseline", labels=False, valid=False)
    return make_topk_predict_step(cfg, model, k=3, mesh=mesh), args


def _build_topk_predict_serve_fleet(ctx: AuditContext):
    """The serve-FLEET predict: the same mesh-pinned serve program at the
    dp4 width a small autoscaled replica runs (serve_mesh over 4 devices).
    The data axis is the only axis, but at width 4 the per-shard batch is
    a quarter of the bucket — so the banked program proves the dp-split
    top-k stays collective-free at the fleet's provisioning unit, not
    just at the dp2 audit minimum."""
    from ..train.steps import make_topk_predict_step

    mesh = ctx.composed_mesh("dp4")
    cfg, model, _, state = ctx.state_for("baseline")
    fn = make_topk_predict_step(cfg, model, k=3, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh))


def _build_train_bf16_reduce(ctx: AuditContext):
    """The bf16-wire gradient-reduction variant of the train step
    (parallel.grad_reduce_dtype=bfloat16): a shard_map fwd/bwd whose
    pmean runs at bf16 with the ZeRO-sharded optimizer update outside —
    a different program (explicit collectives, cast pair around the
    reduction), so it gets its own audit entry per the registry NOTE.
    Reuses the cached baseline model/tx/state (the state layout does not
    depend on the wire dtype)."""
    from ..train.steps import make_train_step

    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.grad_reduce_dtype = "bfloat16"
    fn = make_train_step(cfg, model, tx, mesh=ctx.mesh)
    return fn, (state, ctx.images(), ctx.labels())


def _build_train_accum(ctx: AuditContext):
    """The K=4 accumulated train step (parallel.grad_accum, steps.py
    `_accum_grad_section` + `_scan_microbatches`): a lax.scan over 4
    microbatches with the gradient reduction deferred OUTSIDE the scan —
    a different program (while body, f32 accumulator carry, one explicit
    pmean per optimizer step), so it gets its own audit entry per the
    registry NOTE. Built on the composed dp2 mesh (NOT ctx.mesh, whose
    8-way data axis would leave a per-replica batch of 1, indivisible by
    K=4); the uint8 epilogue runs before the (K, mb, ...) reshape, so
    the raw-pixels→convert→/255 contract is checked through the scan."""
    from ..train.steps import make_train_step

    mesh = ctx.composed_mesh("dp2")
    _, model, tx, state = ctx.state_for("baseline")
    cfg = ctx.tiny_cfg("baseline")
    cfg.parallel.grad_accum = 4
    fn = make_train_step(cfg, model, tx, mesh=mesh)
    return fn, (abstract_state(state, mesh),
                batch_sharded(ctx.images(), mesh),
                batch_sharded(ctx.labels(), mesh))


def build_registry() -> List[StepSpec]:
    """Every jitted step program the framework runs, with its invariants.
    Ordered cheap-to-expensive so a red CLI run fails fast.

    NOTE: a new jitted step factory MUST be registered here — it is then
    donation/epilogue/callback-audited automatically, AND wrapped into the
    dtype pass's contract cells by `dtype_audit.dtype_registry()` (D1–D6
    at the f32-pinned audit precision; name-prefix `train_step` turns on
    the D2 master-weights contract). A NEW PRECISION KNOB additionally needs
    an explicit `#<knob>` cell (plus a `WAIVER_REASONS` entry if it trades
    precision) in `dtype_registry()`.
    The `lint_jit_sites` guard (tests/conftest.py) fails on any
    `jax.jit` site in train/steps.py that is not reachable from a
    registered factory."""
    return [
        StepSpec(
            name="plc_predict",
            factory="ddp_classification_pytorch_tpu.train.steps:make_predict_step",
            build=_build_plc_predict,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="topk_predict",
            factory="ddp_classification_pytorch_tpu.train.steps:make_topk_predict_step",
            build=_build_topk_predict,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="eval_step",
            factory="ddp_classification_pytorch_tpu.train.steps:make_eval_step",
            build=_build_eval,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="nested_eval_step",
            factory="ddp_classification_pytorch_tpu.train.steps:make_nested_eval_step",
            build=_build_nested_eval,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="plc_predict_dp_tp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_predict_step",
            build=_build_plc_predict_dp_tp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="topk_predict_dp_tp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_topk_predict_step",
            build=_build_topk_predict_dp_tp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="topk_predict_serve_dp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_topk_predict_step",
            build=_build_topk_predict_serve_dp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="topk_predict_serve_dp_tp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_topk_predict_step",
            build=_build_topk_predict_serve_dp_tp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="topk_predict_serve_fleet",
            factory="ddp_classification_pytorch_tpu.train.steps:make_topk_predict_step",
            build=_build_topk_predict_serve_fleet,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="eval_step_dp_tp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_eval_step",
            build=_build_eval_dp_tp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="nested_eval_step_dp_tp",
            factory="ddp_classification_pytorch_tpu.train.steps:make_nested_eval_step",
            build=_build_nested_eval_dp_tp,
            no_donate_reason=_EVAL_NO_DONATE,
            uint8_input=True,
        ),
        StepSpec(
            name="train_step",
            factory="ddp_classification_pytorch_tpu.train.steps:make_train_step",
            build=_build_train,
            donate=(0,),
            uint8_input=True,
        ),
        StepSpec(
            name="train_step_survivor",
            factory="ddp_classification_pytorch_tpu.train.steps:make_train_step",
            build=_build_train_survivor,
            donate=(0,),
            uint8_input=True,
        ),
        StepSpec(
            name="train_step_bf16_reduce",
            factory="ddp_classification_pytorch_tpu.train.steps:make_train_step",
            build=_build_train_bf16_reduce,
            donate=(0,),
            uint8_input=True,
            allow_collectives=True,  # the bf16 pmean IS this program
        ),
        StepSpec(
            name="train_step_accum4",
            factory="ddp_classification_pytorch_tpu.train.steps:make_train_step",
            build=_build_train_accum,
            donate=(0,),
            uint8_input=True,
            allow_collectives=True,  # the once-per-K pmean IS this program
        ),
    ]


def audit_entry(spec: StepSpec, ctx: AuditContext) -> List[Finding]:
    """Run every applicable program check for one registry entry; evidence
    (donation byte counts, primitive inventory) lands on `spec.evidence`."""
    findings: List[Finding] = []
    fn, args = spec.build(ctx)

    closed = jax.make_jaxpr(fn)(*args)
    prims = collect_primitives(closed.jaxpr)
    spec.evidence["primitives"] = len(prims)

    if spec.hot_path:
        bad = sorted(prims & CALLBACK_PRIMITIVES)
        if bad:
            findings.append(Finding(
                "callback", spec.name,
                f"host callback primitive(s) in a hot-path program: {bad} "
                "(each is a device→host round trip inside the step)",
                {"primitives": bad}))
    if not spec.allow_collectives:
        bad = sorted(prims & COLLECTIVE_PRIMITIVES)
        if bad:
            findings.append(Finding(
                "collectives", spec.name,
                f"collective primitive(s) in a host-local program: {bad} "
                "(a collective some hosts skip desyncs the fleet's control "
                "collectives — parallel/fleet.py)",
                {"primitives": bad}))
    if spec.uint8_input:
        findings.extend(audit_uint8_epilogue(closed, spec.name))

    if spec.donate:
        dn, ev = audit_donation(fn, args, spec.name, spec.donate)
        findings.extend(dn)
        spec.evidence["donation"] = ev
    elif not spec.no_donate_reason:
        findings.append(Finding(
            "donation", spec.name,
            "entry neither donates nor documents why not — every registered "
            "step must either donate dead buffers or carry a "
            "no_donate_reason (docs/analysis.md)"))
    return findings


def audit_registry(ctx: Optional[AuditContext] = None,
                   registry: Optional[List[StepSpec]] = None
                   ) -> Tuple[List[Finding], List[StepSpec]]:
    """Audit every registry entry; returns (findings, specs-with-evidence)."""
    ctx = ctx or AuditContext()
    specs = registry if registry is not None else build_registry()
    findings: List[Finding] = []
    for spec in specs:
        findings.extend(audit_entry(spec, ctx))
    return findings, specs
