"""AST lint: host-sync idioms in step factories, rc-catalogue discipline.

Three source-level passes complementing the program-level jaxpr audit:

1. **host-sync** — the functions registered in `jaxpr_audit.build_registry`
   (each `StepSpec.factory`) build the jitted hot path; any host-sync idiom
   inside them either forces a device round-trip per step (`.item()`,
   `float(tracer)`, `np.asarray`, `print`) or bakes trace-time wall clock
   into the program (`time.time()`). The reference pays exactly this tax —
   a `.item()` sync per logged step (BASELINE/main.py:284-303) — and the
   framework's metrics design exists to avoid it (train/steps.py docstring).

2. **rc-catalogue** — every deliberate exit in `cli/` must use a code from
   the documented failure-mode matrix (docs/operations.md): supervisors
   classify restart-vs-stop by rc, so an uncatalogued code silently falls
   into the wrong recovery bucket. Literal exits are checked against
   RC_CATALOGUE; non-literal exits are allowed only when they read a
   declared `exit_code`/`code` attribute (SentinelDiverged.exit_code,
   PodAbort.code, …) — the pattern the CLIs use for class-carried codes.

3. **jit-registration** — every `jax.jit` site in `train/steps.py` must
   live inside a factory registered in `jaxpr_audit.build_registry` (or a
   documented delegate/exempt helper): an unregistered jit site is a hot
   program the donation/collective/dtype audits silently never see — the
   registry NOTE's discipline, enforced instead of trusted.

All passes expose `*_source` variants that lint a source string, so the
test fixtures can prove each detector trips on a known-bad sample without
planting bad files in the package.
"""

from __future__ import annotations

import ast
import importlib
import inspect
import os
from typing import Iterable, List, Optional, Sequence, Tuple

from . import Finding

# the documented exit codes (docs/operations.md failure-mode matrix,
# including the elastic pod codes: 10 pod-unviable, 11 pod-reform); signal
# deaths (130/137/143) are raised by the runtime, never by our code, so
# they are deliberately NOT listed
RC_CATALOGUE = frozenset({0, 1, 2, 3, 4, 6, 7, 8, 9, 10, 11})

# call idioms that synchronize the host against the device (or smuggle host
# wall-clock into a trace) when they appear inside a step factory
_HOST_SYNC_DOC = {
    "item": "`.item()` is a blocking device→host sync per call",
    "print": "`print` inside jitted code traces to nothing (or forces a "
             "callback) — metrics must ride the step's outputs",
    "asarray": "`np.asarray` on a tracer forces a device fetch — use jnp",
    "time": "`time.time()` inside a step factory bakes trace-time wall "
            "clock into the compiled program",
    "float": "`float()` on a tracer is a blocking device→host sync",
}


def _called_name(call: ast.Call) -> Tuple[str, Optional[str]]:
    """(attr-or-name, receiver-name) of a call: `np.asarray(x)` →
    ('asarray', 'np'), `print(x)` → ('print', None), `x.item()` →
    ('item', <receiver or None>)."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id, None
    if isinstance(f, ast.Attribute):
        recv = f.value.id if isinstance(f.value, ast.Name) else None
        return f.attr, recv
    return "", None


def _lint_factory_node(fn_node: ast.AST, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        name, recv = _called_name(node)
        where = f"{path}:{node.lineno}"
        if name == "item" and recv != "np":
            findings.append(Finding("host-sync", where, _HOST_SYNC_DOC["item"]))
        elif name == "print" and recv is None:
            findings.append(Finding("host-sync", where, _HOST_SYNC_DOC["print"]))
        elif name == "asarray" and recv in ("np", "numpy"):
            findings.append(Finding("host-sync", where, _HOST_SYNC_DOC["asarray"]))
        elif name == "time" and recv == "time":
            findings.append(Finding("host-sync", where, _HOST_SYNC_DOC["time"]))
        elif name == "float" and recv is None and node.args and not isinstance(
                node.args[0], ast.Constant):
            findings.append(Finding("host-sync", where, _HOST_SYNC_DOC["float"]))
    return findings


def lint_factory_source(src: str, path: str = "<fixture>",
                        function: Optional[str] = None) -> List[Finding]:
    """Host-sync lint over a source string (whole module, or one named
    function) — the fixture-facing surface."""
    tree = ast.parse(src)
    if function is None:
        return _lint_factory_node(tree, path)
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == function:
            return _lint_factory_node(node, path)
    return [Finding("host-sync", path,
                    f"registered factory `{function}` not found in source — "
                    "registry provenance is stale")]


def lint_step_factories(factories: Optional[Iterable[str]] = None
                        ) -> List[Finding]:
    """Host-sync lint over every registered step factory (`module:function`
    provenance strings from jaxpr_audit.build_registry, plus the epilogue
    and shared-skeleton helpers those factories delegate to)."""
    if factories is None:
        from .jaxpr_audit import build_registry

        factories = sorted({s.factory for s in build_registry()} | {
            # delegated helpers that also emit jitted code
            "ddp_classification_pytorch_tpu.train.steps:device_input_epilogue",
            "ddp_classification_pytorch_tpu.train.steps:_build_step",
            "ddp_classification_pytorch_tpu.train.steps:_arcface_sharded_loss",
            "ddp_classification_pytorch_tpu.train.steps:_make_arcface_sharded_eval",
            "ddp_classification_pytorch_tpu.train.steps:_dense_loss_fn",
        })
    findings: List[Finding] = []
    by_module: dict = {}
    for spec in factories:
        module, func = spec.split(":")
        by_module.setdefault(module, []).append(func)
    for module, funcs in sorted(by_module.items()):
        mod = importlib.import_module(module)
        path = inspect.getsourcefile(mod) or module
        with open(path) as f:
            src = f.read()
        rel = os.path.basename(path)
        for func in funcs:
            findings.extend(lint_factory_source(src, rel, function=func))
    return findings


# ------------------------------------------------------- jit registration --

# helpers the registered factories delegate their jit calls to (the shared
# step skeleton and the sharded-eval builder make_eval_step dispatches to)
_JIT_DELEGATES = frozenset({"_build_step", "_make_arcface_sharded_eval"})

# jit sites deliberately OUTSIDE the registry, each with the reviewed why
_JIT_EXEMPT: dict = {}


def lint_jit_source(src: str, registered: Iterable[str],
                    path: str = "<fixture>") -> List[Finding]:
    """jit-registration lint over one source string: every `jax.jit(...)`
    call must sit inside a function in `registered` ∪ delegates ∪ exempt
    (module-level jit sites are never allowed) — the fixture-facing
    surface."""
    allowed = set(registered) | _JIT_DELEGATES | set(_JIT_EXEMPT)
    findings: List[Finding] = []
    tree = ast.parse(src)
    enclosing: dict = {}
    for top in tree.body:
        if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for node in ast.walk(top):
                enclosing[id(node)] = top.name
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name, recv = _called_name(node)
        if not (name == "jit" and recv in (None, "jax")):
            continue
        owner = enclosing.get(id(node))
        if owner is None or owner not in allowed:
            where = f"{path}:{node.lineno}"
            findings.append(Finding(
                "jit-registration", where,
                f"`jax.jit` site in `{owner or '<module level>'}` is not "
                "reachable from a registered step factory — register the "
                "factory in jaxpr_audit.build_registry() (the donation/"
                "collective/dtype audits key off it) or document it in "
                "lint._JIT_EXEMPT",
                {"function": owner}))
    return findings


def lint_jit_sites() -> List[Finding]:
    """jit-registration lint over `train/steps.py`: registered names are
    the registry factories' top-level functions in that module."""
    from .jaxpr_audit import build_registry

    module = "ddp_classification_pytorch_tpu.train.steps"
    registered = {s.factory.split(":")[1] for s in build_registry()
                  if s.factory.startswith(module + ":")}
    mod = importlib.import_module(module)
    path = inspect.getsourcefile(mod) or module
    with open(path) as f:
        src = f.read()
    return lint_jit_source(src, registered, os.path.basename(path))


# ----------------------------------------------------------- rc catalogue --

def _exit_code_findings(call_args: Sequence[ast.expr], where: str,
                        raiser: str) -> List[Finding]:
    if not call_args:  # SystemExit()/sys.exit() → rc 0, catalogued
        return []
    arg = call_args[0]
    if (isinstance(arg, ast.IfExp) and isinstance(arg.body, ast.Constant)
            and isinstance(arg.orelse, ast.Constant)):
        # `0 if ok else 1`: both branches must be catalogued literals
        return (_exit_code_findings([arg.body], where, raiser)
                + _exit_code_findings([arg.orelse], where, raiser))
    if isinstance(arg, ast.Constant):
        if isinstance(arg.value, bool) or not isinstance(arg.value, int):
            return [Finding("rc-catalogue", where,
                            f"{raiser} with a non-integer code {arg.value!r} "
                            "maps to rc 1 — use a catalogued code")]
        if arg.value not in RC_CATALOGUE:
            return [Finding("rc-catalogue", where,
                            f"{raiser}({arg.value}) is not in the documented "
                            f"rc catalogue {sorted(RC_CATALOGUE)} "
                            "(docs/operations.md failure-mode matrix)")]
        return []
    # non-literal: allowed only for declared code attributes
    if isinstance(arg, ast.Attribute) and arg.attr in ("exit_code", "code"):
        return []
    return [Finding("rc-catalogue", where,
                    f"{raiser} with an unrecognized dynamic code "
                    f"`{ast.unparse(arg)}` — use a literal from the catalogue "
                    "or a declared `.exit_code`/`.code` attribute")]


def lint_rc_source(src: str, path: str = "<fixture>") -> List[Finding]:
    """rc-catalogue lint over one source string: every `sys.exit(...)`,
    `os._exit(...)`, and `raise SystemExit(...)` site."""
    findings: List[Finding] = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Call):
            name, recv = _called_name(node)
            where = f"{path}:{node.lineno}"
            if name == "exit" and recv in ("sys", "os"):
                findings.extend(_exit_code_findings(
                    node.args, where, f"{recv}.exit"))
            elif name == "_exit" and recv == "os":
                findings.extend(_exit_code_findings(node.args, where, "os._exit"))
            elif name == "SystemExit":
                findings.extend(_exit_code_findings(node.args, where, "SystemExit"))
    return findings


def lint_rc_sites(paths: Optional[Sequence[str]] = None) -> List[Finding]:
    """rc-catalogue lint over the CLI package (or explicit paths): the
    surface supervisors classify by exit code."""
    if paths is None:
        from .. import cli

        cli_dir = os.path.dirname(inspect.getsourcefile(cli))
        paths = sorted(os.path.join(cli_dir, f) for f in os.listdir(cli_dir)
                       if f.endswith(".py"))
    findings: List[Finding] = []
    for path in paths:
        with open(path) as f:
            findings.extend(lint_rc_source(f.read(), os.path.basename(path)))
    return findings


def run_lint() -> List[Finding]:
    """All source passes — the `--passes lint` entry point."""
    return lint_step_factories() + lint_jit_sites() + lint_rc_sites()
