"""Where the train step computes: every op event of device 0 in the traced
slice put under a PHASE (rows) and a BLOCK (columns), from the op's `op_name`
path alone. Six readers share it (`fwd_device_ms`, `bwd_device_ms`,
`remat_device_ms`, `opt_device_ms`, `bn_device_ms`, `vit_attn_device_ms`).

The phase is what autodiff and the step wrote into the path (first rule that
holds; train/steps.py::STEP_SCOPES names the step's own scopes):

    segment `step.opt` or `step.guard`                 -> opt
    segment `step.input` / `.exchange` / `.metrics`    -> itself
    `rematted_computation`                             -> remat
    `transpose(`                                       -> bwd
    `jvp(`                                             -> fwd
    anything else                                      -> other

The block is the code the op came from: the decoder's scopes as
`_scoped_ops.SCOPES` has them (a ViT's `attn` module among them); a ResNet's
stage (`stem`, `layer1`..`layer4`) x kind (`conv`, `bn`, `residual`, `pool`)
from flax's module names and models/resnet.py's scopes; a ViT's
`patch_embed`, `mlp`, `ln`, `residual`; `head` and `loss` in both; `rest`
where none of these stands in the path (the optimizer, a decoder's norms).

Where a path holds several joined by `;` the first decides, and the table
says how many ms sit in events whose paths disagree on the phase. An event
with no path (the compiler's ragged-dot kernels, which carry no metadata at
all; the `copy-done` / `slice-done` halves of its prefetches into fast
memory, which carry a shape) goes where the op before it on the device's
line went, as `mtp_device_ms` has it. The slice is the one
`_scope_members._load` cuts (whole steps of the step program, the first and
the last left out; `while` events skipped, their bodies' ops counted), and
its events are shared with that module's readers through `ctx`.

The image runner hands its readers no `trace_dir` (runners/train_lm.py
does): where `ctx` has none, the newest trace under benchmark/.cache/trace
that this process wrote is taken, and `ctx["trace_dir"]` set to its cell's
directory. A program without the step's scopes (the parent of the PR that
added them, or an executable from a compile cache older than they are), or
a run without a trace, gives None everywhere: the metric is left out,
nothing raises.
"""

from __future__ import annotations

import glob
import os
import re
import time
from collections import defaultdict

from benchmark import trace_reduce as tr
from benchmark.layers import _scope_members, _scoped_ops

PHASES = ("fwd", "remat", "bwd", "opt", "step.input", "step.exchange",
          "step.metrics", "other")
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "trace")
_KEY = "_phases"


def _segment(names: str):
    """`names` as a whole segment of a path: after its start, a `/` or a `(`,
    before a `/`, a `)` or its end."""
    return re.compile(r"(?<![^/(])(?:" + names + r")(?=[/)]|$)")


_PATH = re.compile(r"jit\(\S*")
_OPT = _segment(r"step\.opt|step\.guard")
_STEP = _segment(r"step\.input|step\.exchange|step\.metrics")
_ANY_STEP = _segment(r"step\.\w+")
_TOP = _segment("loss|head")
_STAGE = _segment(r"(layer\d)_block\d+")
_KIND = _segment("|".join(f"(?P<{kind}>{names})" for kind, names in (
    ("conv", r"Conv_\d+|conv_stem|downsample_conv"),
    ("bn", r"BatchNorm_\d+|bn_stem|downsample_bn|bn"),
    ("residual", "residual"), ("pool", "pool"), ("mlp", "mlp"), ("ln", "ln"),
    ("patch_embed", "patch_embed"))))


def phase_of(path: str) -> str:
    if _OPT.search(path):
        return "opt"
    m = _STEP.search(path)
    if m:
        return m.group(0)
    if "rematted_computation" in path:
        return "remat"
    if "transpose(" in path:
        return "bwd"
    return "fwd" if "jvp(" in path else "other"


def block_of(path: str) -> str:
    scope = _scoped_ops.scope_of(path)
    if scope:
        return scope
    m = _TOP.search(path)
    if m:
        return m.group(0)
    kind = None
    for m in _KIND.finditer(path):
        kind = m.lastgroup  # the innermost
    if kind is None:
        return "rest"
    if kind not in ("conv", "bn", "residual", "pool"):
        return kind
    m = _STAGE.search(path)
    if m is None and kind == "residual":
        return kind  # a ViT's; a ResNet's stands in a block of a stage
    return f"{m.group(1) if m else 'stem'}.{kind}"


def classify(text: str):
    """(phase, block, whether every `;`-joined path gives that phase) of an
    op event's metadata text (the profile writes the path as `jit(...)/...:`
    among the op's category, source lines and shapes); None for a text that
    holds no path."""
    m = _PATH.search(text)
    if m is None:
        return None
    paths = m.group(0).rstrip(":").split(";")
    phase = phase_of(paths[0])
    return phase, block_of(paths[0]), all(phase_of(p) == phase for p in paths[1:])


def _process_start() -> float:
    """When this process started, on the clock file times are on."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        boot = int(next(line for line in f if line.startswith("btime")).split()[1])
    return boot + ticks / os.sysconf("SC_CLK_TCK")


def find_trace_dir(root: str, since: float):
    """The cell directory under `root` that holds the newest `.xplane.pb`
    written after `since`, or None."""
    paths = [p for p in glob.glob(os.path.join(
        root, "*", "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(p) > since]
    if not paths:
        return None
    return os.path.normpath(os.path.join(
        max(paths, key=os.path.getmtime), *[os.pardir] * 4))


def reduce(ops, steps: int):
    """The table of `ops` ([(metadata text, start_ns, dur_ns)], in start
    order) over `steps` steps: {"steps", "scoped": whether any path holds a
    `step.*` segment, "ms": {(phase, block): ms a step}, "phase_ms",
    "block_ms", "all_ms" (unions of intervals, ms a step), "other": the five
    largest `other` events' [text, ms a step], "disagree": (events, ms a
    step) whose joined paths give different phases}."""
    cells, other = defaultdict(list), defaultdict(float)
    at, odd, odd_ns = ("other", "rest"), 0, 0.0
    seen = {}  # a step's few thousand ops, each met once a step
    for text, start, dur in ops:
        if text not in seen:
            seen[text] = classify(text)
        found = seen[text]
        if found is not None:  # else: where the op before it went
            at = found[:2]
            if not found[2]:
                odd, odd_ns = odd + 1, odd_ns + dur
        cells[at].append((start, start + dur))
        if at[0] == "other":
            other[text] += dur

    def ms(groups):
        return {k: tr.total(tr.union(v)) * 1e-6 / steps for k, v in groups.items()}

    by_phase, by_block = defaultdict(list), defaultdict(list)
    for (phase, block), spans in cells.items():
        by_phase[phase] += spans
        by_block[block] += spans
    everything = [x for spans in cells.values() for x in spans]
    return {"steps": steps, "scoped": any(map(_ANY_STEP.search, seen)),
            "ms": ms(cells),
            "phase_ms": ms(by_phase), "block_ms": ms(by_block),
            "all_ms": tr.total(tr.union(everything)) * 1e-6 / steps,
            "other": [[t, d * 1e-6 / steps] for t, d in
                      sorted(other.items(), key=lambda kv: -kv[1])[:5]],
            "disagree": (odd, odd_ns * 1e-6 / steps)}


def table(ctx):
    """`reduce` of the traced slice, logged once; None without a trace or
    where the traced executable has no step scopes."""
    if _KEY not in ctx:
        ctx[_KEY] = _cut(ctx)
    return ctx[_KEY]


def _cut(ctx):
    if ctx.get("trace") is None:
        return None
    if "trace_dir" not in ctx:
        found = find_trace_dir(TRACE_ROOT, _process_start())
        if found is None:
            return None
        ctx["trace_dir"] = found
    t0 = time.perf_counter()
    if _scope_members._KEY not in ctx:
        ctx[_scope_members._KEY] = _scope_members._load(ctx)
    if ctx[_scope_members._KEY] is None:
        return None
    t1 = time.perf_counter()
    t = reduce(*ctx[_scope_members._KEY])
    _log(t)
    print(f"[bench]   this table took {time.perf_counter() - t0:.2f} s, "
          f"{t1 - t0:.2f} of them the trace's load (0 where a reader before "
          f"this one had loaded it)", flush=True)
    return t if t["scoped"] else None


def phase_ms(ctx, phase: str):
    """Device ms a step of the op events in `phase`, None where none is."""
    t = table(ctx)
    return None if t is None else t["phase_ms"].get(phase)


def block_ms(ctx, pattern: str):
    """Device ms a step of the blocks whose name matches `pattern` whole,
    every phase; None where none does."""
    t = table(ctx)
    if t is None:
        return None
    hit = [v for k, v in t["block_ms"].items() if re.fullmatch(pattern, k)]
    return float(sum(hit)) if hit else None


def _log(t) -> None:
    def log(msg):
        print(f"[bench] {msg}", flush=True)

    if not t["scoped"]:
        log("the traced executable has no step scopes: a compile cache older "
            "than the scopes?")
        return
    phases = [p for p in PHASES if p in t["phase_ms"]]
    blocks = sorted(t["block_ms"], key=lambda b: -t["block_ms"][b])
    log(f"device time by phase (ms a step over {t['steps']} steps; rows the "
        f"phases, columns the blocks, union of the op events in each):")
    log("  " + " | ".join(["phase"] + blocks + ["row"]))
    for p in phases:
        log("  " + " | ".join(
            [p] + [f"{t['ms'].get((p, b), 0.0):.3f}" for b in blocks]
            + [f"{t['phase_ms'][p]:.3f}"]))
    log("  " + " | ".join(["column"] + [f"{t['block_ms'][b]:.3f}" for b in blocks]
                          + [f"{sum(t['phase_ms'].values()):.3f}"]))
    rows = sum(t["phase_ms"].values())
    off = abs(rows - t["all_ms"]) / t["all_ms"]
    log(f"  sum of the rows {rows:.3f}; union of all op events {t['all_ms']:.3f}"
        f" ({'within' if off <= 0.01 else 'OUTSIDE'} 1 %: {100 * off:.2f} %)")
    log(f"  {t['disagree'][0]} events ({t['disagree'][1]:.3f} ms a step) hold "
        f"`;`-joined paths that disagree on the phase")
    if t["other"]:
        # the shared events carry no HLO name: the metadata's first line (the
        # op's category, the fused computation's name) and its path stand for it
        log("  largest of `other` (ms a step, metadata's head and path): "
            + "; ".join(f"{v:.3f} {text.splitlines()[0][:60] if text else ''!r} "
                        f"{(_PATH.search(text) or [''])[0]!r}"
                        for text, v in t["other"]))
