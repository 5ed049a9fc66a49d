"""Ops-script and document consistency guards.

Scripts are not exercised by the unit suite, so give them the cheap static
guards: every shell script must parse, and every repo path a script
references must exist — a renamed helper breaks the referencing script
exactly when someone reaches for it. The documents get the same guard: a
file one of them names in code must be in the tree.
"""

import functools
import glob
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


def _shell_scripts():
    return sorted(
        os.path.join(SCRIPTS, f) for f in os.listdir(SCRIPTS)
        if f.endswith(".sh")
    )


def test_shell_scripts_parse():
    assert _shell_scripts(), "scripts/*.sh disappeared"
    for path in _shell_scripts():
        p = subprocess.run(["bash", "-n", path], capture_output=True, timeout=30)
        assert p.returncode == 0, (path, p.stderr.decode())


def test_script_repo_references_exist():
    """Repo-relative paths named in shell scripts must exist: `python
    scripts/foo.py` and `python -m package.module`."""
    missing = []
    for path in _shell_scripts():
        with open(path) as f:
            # comment lines may cite reference-world commands
            # (torch.distributed.launch) that rightly don't exist here
            text = "\n".join(
                ln for ln in f.read().splitlines()
                if not ln.lstrip().startswith("#")
            )
        for m in re.finditer(r"\bscripts/[\w./-]+\.(?:py|sh)\b", text):
            if not os.path.exists(os.path.join(REPO, m.group(0))):
                missing.append((os.path.basename(path), m.group(0)))
        for m in re.finditer(r"\bpython -m ([\w.]+)\b", text):
            mod = m.group(1).replace(".", "/")
            if not (os.path.exists(os.path.join(REPO, mod + ".py"))
                    or os.path.isdir(os.path.join(REPO, mod))):
                missing.append((os.path.basename(path), m.group(1)))
    assert not missing, missing


# documents that describe the tree as it is (CHANGES.md, PERF.md and
# ROADMAP.md are history: they name what was deleted)
_DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
         + sorted(os.path.relpath(p, REPO)
                  for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))
_DOC_PATH = re.compile(
    r"(?<![\w/.$<>~{}-])((?:[\w.-]+/)*[\w.-]+\.(?:py|sh|json))\b")
# the upstream repo's files, cited as the reference
_UPSTREAM = re.compile(r"^(?:(?:BASELINE|ARCFACE|CDR|NESTED|PLC)/.*|main\.py)$")
# written by a run, under a directory the reader chose
_WRITTEN_BY_A_RUN = {"report.json", "seed_spec.json", "manifest.json",
                     "chiprun_out/chip_smoke.json", ".chip_smoke/chip_smoke.json"}


@functools.lru_cache(maxsize=None)
def _file_names_in_tree():
    names = set()
    for _, dirs, files in os.walk(REPO):
        # hidden directories hold caches and unpacked copies of other commits
        dirs[:] = [x for x in dirs if not x.startswith(".") and x != "chiprun_out"]
        names.update(files)
    return frozenset(names)


@pytest.mark.parametrize("doc", _DOCS)
def test_document_names_only_files_that_exist(doc):
    """Every `*.py` / `*.sh` / `*.json` a document names in a code span or a
    command exists: with a directory part, under the checkout or the
    package; bare, somewhere in the tree under that name."""
    with open(os.path.join(REPO, doc)) as f:
        code = "\n".join(re.findall(r"```.*?```|`[^`\n]+`", f.read(), flags=re.S))
    missing = set()
    for path in set(_DOC_PATH.findall(code)) - _WRITTEN_BY_A_RUN:
        if _UPSTREAM.match(path):
            continue
        if "/" in path:
            found = any(os.path.exists(os.path.join(root, path)) for root in
                        (REPO, os.path.join(REPO, "ddp_classification_pytorch_tpu")))
        else:
            found = path in _file_names_in_tree()
        if not found:
            missing.add(path)
    assert not missing, (doc, sorted(missing))


def _script_body(name):
    with open(os.path.join(SCRIPTS, name)) as f:
        return "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("#"))


def test_serve_script_flags_match_cli():
    """scripts/serve.sh must stay in sync with cli.serve: every --flag the
    launcher passes has to exist in the CLI parser, or the launcher breaks
    exactly when someone reaches for it (the drift failure mode this file
    exists to guard)."""
    from ddp_classification_pytorch_tpu.cli.serve import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    body = _script_body("serve.sh")
    assert "ddp_classification_pytorch_tpu.cli.serve" in body
    passed = set(re.findall(r"(?<![\w-])--[a-z_]+", body))
    assert passed, "serve.sh passes no flags — launcher gutted?"
    unknown = sorted(passed - known)
    assert not unknown, f"serve.sh passes flags cli.serve rejects: {unknown}"


def test_chaos_drill_flags_match_train_cli():
    """chaos_drill.sh phases drive cli.train through supervise.sh: every
    --flag it passes must exist in the train parser, and the pod phases'
    load-bearing pieces (--multihost, peer_dead, CHAOS_HOST aiming, the
    FLEET_ rendezvous knobs) must stay present — a silently dropped flag
    would skip the pod drill without anyone noticing."""
    from ddp_classification_pytorch_tpu.cli.scenario import (
        build_parser as scenario_parser,
    )
    from ddp_classification_pytorch_tpu.cli.train import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    # phase 8 delegates to scripts/scenario.sh → cli.scenario; its flags
    # are legal in the drill body too
    for action in scenario_parser()._actions:
        known.update(action.option_strings)
    body = _script_body("chaos_drill.sh")
    # XLA_FLAGS=--xla_... is an env assignment, not a CLI flag
    cli_body = re.sub(r"XLA_FLAGS=\S+", "", body)
    passed = set(re.findall(r"(?<![\w-])--[a-z_]+", cli_body))
    unknown = sorted(passed - known)
    assert not unknown, f"chaos_drill.sh passes flags cli.train rejects: {unknown}"
    for needle in ("--multihost", "peer_dead@step=", "CHAOS_HOST=1",
                   "FLEET_COORDINATOR=", "FLEET_PROCESS_ID=",
                   "--hang_timeout_s", "nan_loss@step=",
                   "ckpt_e1.msgpack.corrupt",
                   # the elastic phases' load-bearing pieces
                   "host_lost@step=", "FLEET_ELASTIC=",
                   "FLEET_MIN_PROCESSES=", "FLEET_HOST_ID=",
                   # phase 8: the train→serve scenario and the evidence it
                   # must find in the recorded event log
                   "scripts/scenario.sh", "GREEN: S1 verified-serve",
                   '"kind": "publish_torn"', '"kind": "watcher_error"',
                   '"kind": "reform"', '"kind": "drain_begin"', "rc=11"):
        assert needle in body, f"chaos_drill.sh lost its {needle!r} phase piece"


def test_scenario_script_flags_match_cli():
    """scripts/scenario.sh must stay in sync with cli.scenario: every
    --flag it passes has to exist in the scenario parser, and its default
    spec must keep staging every fault family the drill exists to prove
    (a silently dropped fault would hollow out phase 8)."""
    from ddp_classification_pytorch_tpu.cli.scenario import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    body = _script_body("scenario.sh")
    assert "ddp_classification_pytorch_tpu.cli.scenario" in body
    passed = set(re.findall(r"(?<![\w-])--[a-z_]+", body))
    assert passed, "scenario.sh passes no flags — launcher gutted?"
    unknown = sorted(passed - known)
    assert not unknown, \
        f"scenario.sh passes flags cli.scenario rejects: {unknown}"
    for needle in ("ckpt_io@epoch=", "publish_corrupt@epoch=",
                   "nan_loss@step=", "host_lost@step=", "watcher_io@poll=",
                   "drain_replica", "JAX_PLATFORMS=cpu"):
        assert needle in body, \
            f"scenario.sh default spec lost its {needle!r} fault piece"


def test_fuzz_script_flags_match_cli():
    """scripts/fuzz.sh must stay in sync with cli.fuzz: every --flag it
    passes has to exist in the fuzz parser, and it must keep the seeded
    knobs (seed/budget/runner) wired through the environment — a dropped
    knob would quietly make nightly fuzz runs unreproducible."""
    from ddp_classification_pytorch_tpu.cli.fuzz import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    body = _script_body("fuzz.sh")
    assert "ddp_classification_pytorch_tpu.cli.fuzz" in body
    passed = set(re.findall(r"(?<![\w-])--[a-z_]+", body))
    assert passed, "fuzz.sh passes no flags — launcher gutted?"
    unknown = sorted(passed - known)
    assert not unknown, f"fuzz.sh passes flags cli.fuzz rejects: {unknown}"
    for needle in ("FUZZ_SEED", "FUZZ_BUDGET", "FUZZ_RUNNER",
                   "JAX_PLATFORMS=cpu"):
        assert needle in body, f"fuzz.sh lost its {needle!r} knob"


def test_lint_script_flags_match_analyze_cli():
    """scripts/lint.sh is the CI gate for cli.analyze: every --flag it
    passes must exist in the analyze parser, and it must actually run the
    analyzer (the drift failure mode this file exists to guard)."""
    from ddp_classification_pytorch_tpu.cli.analyze import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    body = _script_body("lint.sh")
    assert "ddp_classification_pytorch_tpu.cli.analyze" in body
    # hyphen-aware: `--diff-baseline` must match whole, not truncate to
    # `--diff` (which the parser would reject)
    passed = set(re.findall(r"(?<![\w-])--[a-z_]+(?:-[a-z_]+)*", body))
    assert passed, "lint.sh passes no flags — gate gutted?"
    unknown = sorted(passed - known)
    assert not unknown, f"lint.sh passes flags cli.analyze rejects: {unknown}"
    # the gate must run ALL pass families, on CPU, and diff the committed
    # program baseline (the sharding/comms regression fence)
    assert "jaxpr" in body and "lint" in body and "sharding" in body
    assert "dtype" in body, "lint.sh stopped running the dtype numerics pass"
    assert "--diff-baseline" in body
    assert "JAX_PLATFORMS=cpu" in body


def test_zero_opt_knobs_locked_in_cli_train():
    """The ZeRO-1 / wire-dtype knobs stay addressable from cli.train with
    their value sets (underscore spelling, feeds cfg.parallel):
    docs/performance.md's knob tables name them."""
    from ddp_classification_pytorch_tpu.cli.train import build_parser

    actions = {}
    for action in build_parser()._actions:
        for s in action.option_strings:
            actions[s] = action
    assert "--zero_opt" in actions, "cli.train lost --zero_opt"
    assert set(actions["--zero_opt"].choices) == {"", "auto", "on", "off"}
    assert "--grad_reduce_dtype" in actions, \
        "cli.train lost --grad_reduce_dtype"
    assert set(actions["--grad_reduce_dtype"].choices) == \
        {"", "float32", "bfloat16"}


def test_grad_accum_h2d_knobs_locked_in_cli_train():
    """The grad-accum / H2D-overlap knobs stay addressable from cli.train
    (underscore `--grad_accum`, dashed `--h2d-overlap`; feed
    cfg.parallel/cfg.data). Same drift guard as the ZeRO knobs above."""
    from ddp_classification_pytorch_tpu.cli.train import build_parser

    known = set()
    actions = {}
    for action in build_parser()._actions:
        known.update(action.option_strings)
        for s in action.option_strings:
            actions[s] = action
    assert "--grad_accum" in known, "cli.train lost --grad_accum"
    assert actions["--grad_accum"].type is int
    assert "--h2d-overlap" in known, "cli.train lost --h2d-overlap"


def test_serve_dp_aot_knobs_locked():
    """The dp-serving / AOT-sidecar knobs must stay addressable in both
    spellings on cli.serve (scripts use underscores, operators type
    hyphens)."""
    from ddp_classification_pytorch_tpu.cli.serve import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    for flag in ("--serve_devices", "--serve-devices",
                 "--aot_cache", "--aot-cache"):
        assert flag in known, f"cli.serve lost {flag}"


def test_serve_fleet_admission_knobs_locked():
    """The serve-fleet control-plane knobs must stay addressable in both
    spellings on cli.serve (scripts use underscores, operators type
    hyphens), scripts/serve.sh must keep its env→flag plumbing for them,
    and chaos_drill.sh phase 9 must keep asserting the fleet evidence it
    exists to prove (drain token, load spike, autoscale answer, the S5
    verdict line) — drop any of these and the rolling-wave/SLO story
    silently stops being exercised."""
    from ddp_classification_pytorch_tpu.cli.serve import build_parser

    known = set()
    for action in build_parser()._actions:
        known.update(action.option_strings)
    for flag in ("--fleet_dir", "--fleet-dir",
                 "--fleet_replica", "--fleet-replica",
                 "--fleet_ttl_s", "--fleet-ttl-s",
                 "--admission_deadline_ms", "--admission-deadline-ms",
                 "--admission_tenants", "--admission-tenants"):
        assert flag in known, f"cli.serve lost {flag}"
    body = _script_body("serve.sh")
    for knob in ("FLEET_DIR", "FLEET_REPLICA", "FLEET_TTL_S",
                 "ADMISSION_DEADLINE_MS", "ADMISSION_TENANTS"):
        assert knob in body, f"serve.sh lost its {knob} env knob"
    drill = _script_body("chaos_drill.sh")
    for needle in ('"kind": "drain_token_acquire"', '"kind": "spike_load"',
                   '"kind": "scale_out"', "kill_replica_during_wave",
                   "S5 fleet", "max_replicas", "fleet_ttl_s",
                   "admission_deadline_ms", "scale_out_deadline_s"):
        assert needle in drill, \
            f"chaos_drill.sh lost its {needle!r} fleet-drill piece"
