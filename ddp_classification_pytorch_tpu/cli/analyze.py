"""`analyze` entrypoint — run the program-invariant analyzer over the repo.

    python -m ddp_classification_pytorch_tpu.cli.analyze            # all passes
    python -m ddp_classification_pytorch_tpu.cli.analyze --passes lint
    python -m ddp_classification_pytorch_tpu.cli.analyze --diff-baseline
    python -m ddp_classification_pytorch_tpu.cli.analyze --update-baseline
    python -m ddp_classification_pytorch_tpu.cli.analyze --list     # inventory

Exit discipline (same classes as cli.train / cli.serve, docs/operations.md):

- **rc 0** — every invariant holds (donation aliasing, callback-free hot
  paths, uint8 epilogue, collective-free eval/serve programs, host-sync-free
  step factories, catalogued CLI exit codes, sharding/comms policies, the
  dtype pass's numerics contracts D1–D6, and — under `--diff-baseline` —
  no drift beyond the committed baseline's tolerances);
- **rc 1** — findings: each printed as `[check] where: message`, machine
  copies via `--json`;
- **rc 2** — usage/config error (unknown pass name, argparse errors, a
  backend that cannot host the composed audit meshes).

The jaxpr/sharding passes lower real step factories on a tiny synthetic
config, so they run in seconds on CPU; analysis never needs (or touches) an
accelerator — the backend is pinned to CPU unless `--platform` overrides
it, and a multi-device CPU topology is self-forced (XLA_FLAGS
`--xla_force_host_platform_device_count=8`) so the composed 2×1/2×2 audit
meshes exist on any host — a standalone `--diff-baseline` run matches the
environment the committed baseline was generated in. CI wrapper:
`scripts/lint.sh`; runbook for a red finding: docs/analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

PASSES = ("jaxpr", "lint", "sharding", "dtype")

# the composed audit meshes (dp2, dp2tp2) need ≥4 devices; on CPU we force
# a virtual topology BEFORE backend init so baselines are host-independent
_FORCED_CPU_DEVICES = 8


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ddp_classification_pytorch_tpu.cli.analyze",
        description="program-invariant analyzer: jaxpr/HLO donation audit, "
                    "host-sync & rc-catalogue linting",
    )
    p.add_argument("--passes", default=",".join(PASSES),
                   help="comma list of passes to run: jaxpr (trace/compile "
                        "the step registry), lint (AST passes), sharding "
                        "(compile the program×mesh matrix: collective "
                        "inventory, sharding table, memory budget), dtype "
                        "(numerics contracts D1-D6 over every cell); "
                        "default: all")
    p.add_argument("--dtype", action="store_true",
                   help="shorthand: add the dtype pass to --passes")
    p.add_argument("--arch", default="resnet18",
                   help="backbone for the audit's tiny traced config "
                        "(invariants are program-structure properties, "
                        "independent of scale)")
    p.add_argument("--image_size", type=int, default=32)
    p.add_argument("--num_classes", type=int, default=8)
    p.add_argument("--batchsize", "-b", type=int, default=8,
                   help="synthetic batch aval (must divide the device count "
                        "for the shard_map entry)")
    p.add_argument("--json", default="",
                   help="also write findings + registry evidence as JSON")
    p.add_argument("--list", action="store_true",
                   help="print the registry + invariant inventory and exit 0")
    p.add_argument("--rc-paths", nargs="*", default=None,
                   help="explicit files for the rc-catalogue lint "
                        "(default: the cli/ package)")
    p.add_argument("--platform", default="", choices=["", "cpu", "tpu"],
                   help="JAX platform for the jaxpr pass (default cpu: "
                        "analysis must never burn — or hang on — an "
                        "accelerator lease)")
    p.add_argument("--baseline", default="",
                   help="program-baseline JSON path (default: the "
                        "checked-in analysis/baselines.json)")
    p.add_argument("--diff-baseline", "--diff_baseline",
                   dest="diff_baseline", action="store_true",
                   help="diff the sharding pass's records against the "
                        "committed baseline; drift beyond tolerances "
                        "(new collective kind, payload/peak-HBM growth, "
                        "sharding downgrade, donation regression) is rc 1")
    p.add_argument("--update-baseline", "--update_baseline",
                   dest="update_baseline", action="store_true",
                   help="regenerate the baseline file from this run (with "
                        "a provenance header) instead of diffing — commit "
                        "the result; runbook in docs/analysis.md")
    return p


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    passes = tuple(s.strip() for s in args.passes.split(",") if s.strip())
    unknown = [s for s in passes if s not in PASSES]
    if unknown or not passes:
        # deterministic config error → rc 2, the code supervisors never retry
        print(f"[analyze] config error: unknown pass(es) {unknown or passes}; "
              f"choose from {list(PASSES)}", file=sys.stderr)
        raise SystemExit(2)
    if args.dtype and "dtype" not in passes:
        passes = passes + ("dtype",)
    if args.diff_baseline or args.update_baseline:
        # the baseline file is the sharding + dtype passes' joint artifact
        passes += tuple(p for p in ("sharding", "dtype") if p not in passes)

    if ("jaxpr" in passes or "sharding" in passes or "dtype" in passes) and (
            args.platform or "cpu") == "cpu":
        # the registry's dp×tp entries and the sharded matrix need the
        # composed 2×1/2×2 meshes: force a virtual multi-device CPU
        # topology before the backend initializes (a no-op if the caller
        # already forced one, e.g. the test suite's conftest), so a
        # standalone `--diff-baseline` reproduces the committed baseline's
        # environment on any host
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{_FORCED_CPU_DEVICES}").strip()

    from ..analysis.jaxpr_audit import build_registry

    if args.list:
        print("registered step programs (jaxpr pass):")
        for spec in build_registry():
            props = []
            if spec.donate:
                props.append(f"donates args {list(spec.donate)} (must alias)")
            else:
                props.append("no-donate (documented)")
            if spec.hot_path:
                props.append("callback-free")
            if not spec.allow_collectives:
                props.append("collective-free")
            if spec.uint8_input:
                props.append("uint8→epilogue")
            print(f"  {spec.name:22s} {spec.factory}")
            print(f"  {'':22s} invariants: {', '.join(props)}")
        print("lint pass: host-sync idioms in the factories above; "
              "jit-registration guard over train/steps.py; "
              "rc catalogue over cli/ exits (docs/operations.md matrix)")
        from ..analysis.sharding_audit import sharded_registry

        print("sharding pass (program × composed mesh matrix):")
        for case in sharded_registry():
            print(f"  {case.key:24s} policy: "
                  f"allowed={list(case.policy.allowed_kinds)}"
                  + (" + gradient all-reduce floor"
                     if case.policy.require_grad_allreduce else
                     f", per-op ≤ {case.policy.small_bytes}B")
                  + f", wire≥{case.wire_dtype}")
        from ..analysis.dtype_audit import dtype_registry

        print("dtype pass (program × precision-config cells, contracts "
              "D1-D6):")
        for dcase in dtype_registry():
            waived = ",".join(sorted(dcase.waivers)) or "none"
            print(f"  {dcase.name:34s} "
                  f"{'train (D2 master-weights)' if dcase.train else 'eval'}"
                  f", waivers: {waived}")
        return

    findings = []
    evidence = {}

    if "lint" in passes:
        from ..analysis.lint import (lint_jit_sites, lint_rc_sites,
                                     lint_step_factories)

        findings += lint_step_factories()
        findings += lint_jit_sites()
        findings += lint_rc_sites(paths=args.rc_paths)

    ctx = None
    if "jaxpr" in passes or "sharding" in passes or "dtype" in passes:
        import jax

        # analysis is host-side program inspection: pin CPU so the linter
        # never takes the chip from a job that needs it
        jax.config.update("jax_platforms", args.platform or "cpu")
        from ..analysis.jaxpr_audit import AuditContext

        ctx = AuditContext(arch=args.arch, image_size=args.image_size,
                           num_classes=args.num_classes, batch=args.batchsize)
        if ("sharding" in passes or "dtype" in passes) \
                and jax.device_count() < 4:
            print(f"[analyze] config error: the sharding/dtype passes need "
                  f"≥4 devices for the composed audit meshes, have "
                  f"{jax.device_count()} (force more via XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)",
                  file=sys.stderr)
            raise SystemExit(2)

    if "jaxpr" in passes:
        from ..analysis.jaxpr_audit import audit_registry

        jx_findings, specs = audit_registry(ctx)
        findings += jx_findings
        for spec in specs:
            evidence[spec.name] = spec.evidence
            don = spec.evidence.get("donation")
            if don:
                print(f"[analyze] {spec.name}: donated={don['donated_bytes']}B "
                      f"aliased={don['aliased_bytes']}B "
                      f"coverage={don['donation_coverage']}")

    records = None
    if "sharding" in passes:
        from ..analysis.sharding_audit import audit_sharded_registry

        sh_findings, records = audit_sharded_registry(ctx)
        findings += sh_findings
        evidence["sharded"] = records
        for key, rec in records.items():
            print(f"[analyze] {key}: "
                  f"collectives={rec['collective_bytes_per_step']}B/step "
                  f"({'+'.join(sorted(rec['collectives'])) or 'none'}) "
                  f"peak_hbm={rec['peak_hbm_bytes']}B"
                  + (f" coverage={rec['donation_coverage']}"
                     if rec["donation_coverage"] is not None else ""))

    dtype_records = None
    if "dtype" in passes:
        from ..analysis.dtype_audit import audit_dtype_registry

        dt_findings, dtype_records = audit_dtype_registry(ctx)
        findings += dt_findings
        evidence["dtype"] = dtype_records
        for key, rec in dtype_records.items():
            print(f"[analyze] {key}: bf16_ops={rec['bf16_op_fraction']} "
                  f"casts={sum(rec['casts'].values())} "
                  f"wire={'+'.join(rec['collective_dtypes']) or 'none'} "
                  f"waivers={','.join(rec['waivers']) or 'none'}")

    if args.update_baseline:
        from ..analysis import baseline as baselib

        path = baselib.write_baseline(
            records or {}, args.baseline or None,
            context={"arch": args.arch, "image_size": args.image_size,
                     "num_classes": args.num_classes,
                     "batch": args.batchsize},
            dtype_records=dtype_records)
        print(f"[analyze] baseline written: {path} "
              f"({len(records or {})} sharded + "
              f"{len(dtype_records or {})} dtype cells) — review + commit "
              "the diff")
    elif args.diff_baseline:
        from ..analysis import baseline as baselib
        from ..analysis.dtype_audit import diff_dtype_baseline

        try:
            base = baselib.load_baseline(args.baseline or None)
        except FileNotFoundError as e:
            print(f"[analyze] config error: {e}", file=sys.stderr)
            raise SystemExit(2)
        findings += baselib.diff_baseline(records or {}, base)
        findings += diff_dtype_baseline(dtype_records or {}, base)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"findings": [vars(fd) for fd in findings],
                       "evidence": evidence}, f, indent=2, default=str)

    for fd in findings:
        print(str(fd), file=sys.stderr)
    if findings:
        print(f"[analyze] {len(findings)} finding(s) — see docs/analysis.md "
              "for the runbook", file=sys.stderr)
        raise SystemExit(1)
    ran = "+".join(passes)
    print(f"[analyze] clean: {ran} pass(es), "
          f"{len(evidence) or 'no'} programs audited")


if __name__ == "__main__":
    main()
