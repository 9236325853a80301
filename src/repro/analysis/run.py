"""CI gate: run both analysis layers and fail on unsuppressed findings.

    PYTHONPATH=src python -m repro.analysis.run --json BENCH_analysis.json

Layer 1 (pallas_audit) sweeps every registered kernel program over the
serving bucket rungs AND the per-client dry-run shard shapes of both
production meshes (k=256, k=512) -- pure index-map evaluation, no
devices.  Layer 2 (hlo_lint) compiles the hot paths and lints the
optimized HLO; ``--dryrun-meshes`` additionally lowers the full
production-mesh dry-run entries, which needs 512 forced host devices
-- so XLA_FLAGS is set HERE, before jax is imported (the same pattern
as launch/dryrun.py; jax pins the device count at first init)."""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="static kernel + compiled-HLO analysis gate")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the full report to PATH")
    ap.add_argument("--dryrun-meshes", action="store_true",
                    help="also lint the k=256/k=512 production-mesh "
                         "lowerings (slow; forces 512 host devices)")
    ap.add_argument("--skip-hlo", action="store_true",
                    help="Layer 1 only (no compilation)")
    args = ap.parse_args(argv)

    # before ANY jax import: device count is pinned at first init
    n_dev = 512 if args.dryrun_meshes else 8
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev} "
        + os.environ.get("XLA_FLAGS", ""))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from repro.analysis import hlo_lint, pallas_audit

    kernel_records, kernel_findings = pallas_audit.audit_all()
    print(f"[analysis] layer 1: {len(kernel_records)} kernel cases, "
          f"{len(kernel_findings)} findings")

    hlo_records: list[dict] = []
    hlo_findings: list[hlo_lint.Finding] = []
    if not args.skip_hlo:
        targets = hlo_lint.default_targets()
        if args.dryrun_meshes:
            targets += hlo_lint.dryrun_mesh_targets()
        hlo_records, hlo_findings = hlo_lint.lint_all(targets)
        print(f"[analysis] layer 2: {len(hlo_records)} lint targets, "
              f"{len(hlo_findings)} findings")

    all_findings = ([{"rule": f.rule, "target": f.kernel,
                      "case": f.case, "detail": f.detail}
                     for f in kernel_findings]
                    + [dict(f._asdict()) for f in hlo_findings])
    live_hlo, waived = hlo_lint.apply_suppressions(hlo_findings)
    live = len(kernel_findings) + len(live_hlo)

    report = {
        "rules": dict(hlo_lint.RULES,
                      **{"BLOCK-001": "every block in bounds",
                         "COVER-001": "every output block written",
                         "RACE-001": "revisits are declared accumulation",
                         "VMEM-001": "blocks+scratch fit 16 MiB",
                         "TILE-001": "blocks meet Mosaic's (8, 128) "
                                     "tiling rule"}),
        "kernel_cases": kernel_records,
        "hlo_targets": hlo_records,
        "findings": all_findings,
        "suppressed": waived,
        "unsuppressed_count": live,
    }
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
        print(f"[analysis] report -> {args.json}")

    for f in kernel_findings:
        print(f"FINDING {f.rule} {f.kernel} [{f.case}]: {f.detail}")
    for f in live_hlo:
        print(f"FINDING {f.rule} {f.target}: {f.detail}")
    for w in waived:
        print(f"suppressed {w['rule']} {w['target']}: "
              f"{w['justification']}")

    if live:
        print(f"[analysis] FAIL: {live} unsuppressed findings")
        return 1
    print("[analysis] OK: zero unsuppressed findings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
