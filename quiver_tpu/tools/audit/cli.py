"""graftaudit CLI.

    python -m quiver_tpu.tools.audit [--json] [--sarif PATH] \
        [--select rules] [--ignore rules] [--targets names] \
        [--changed BASE] [--list-rules] [--list-targets] \
        [--mem-table [--mem-xla]]

Exit codes (stable, for CI — same contract as graftlint):
  0 — clean (waived findings are fine)
  1 — findings (including targets that fail to build)
  2 — usage error (unknown rule/family/target, bad --changed base)

The auditor traces and lowers programs but never executes them: it runs
on CPU with a forced 2-device host platform. Those env knobs must be set
BEFORE jax initializes its backend, so this module touches jax only
inside :func:`main` after pinning the environment (a no-op when the
process — e.g. pytest via conftest — already configured a mesh).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

__all__ = ["main"]


def _pin_platform() -> None:
    if "jax" in sys.modules:
        # a host process (a pytest run) may already have chosen a
        # backend; flipping jax_platforms after init would poison its
        # later work. Merely-imported jax must still be pinned.
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m quiver_tpu.tools.audit",
        description="graftaudit — jaxpr/StableHLO-level program auditor: "
                    "collective parity, metric stripping, donation, dtype "
                    "discipline, constant bloat, the comm budget and the "
                    "graftmem memory family (peak-HBM, replication, VMEM, "
                    "padding), proven on lowered IR without executing a "
                    "step",
    )
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    p.add_argument("--select", default=None,
                   help="comma-separated rules/families to run "
                        "(default: all)")
    p.add_argument("--ignore", default=None,
                   help="comma-separated rules/families to skip")
    p.add_argument("--targets", default=None,
                   help="comma-separated registry targets to audit "
                        "(default: all)")
    p.add_argument("--changed", default=None, metavar="BASE",
                   help="audit only targets whose declared sources "
                        "changed vs the given git base")
    p.add_argument("--sarif", default=None, metavar="PATH",
                   help="write a SARIF 2.1.0 report to PATH ('-' for "
                        "stdout) for CI annotation")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule registry (grouped by family) "
                        "and exit")
    p.add_argument("--list-targets", action="store_true",
                   help="print the audited program registry and exit")
    p.add_argument("--mem-table", action="store_true",
                   help="print the graftmem per-target budget table "
                        "(est peak / args / out / budget / headroom) "
                        "and exit")
    p.add_argument("--mem-xla", action="store_true",
                   help="with --mem-table: compile each target and join "
                        "XLA memory_analysis() peaks as a cross-check "
                        "column (the only compiling audit path)")
    return p


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    from .rules import FAMILIES, family_of, rule_docs

    if args.list_rules:
        docs = rule_docs()
        for fam, rules in FAMILIES.items():
            print(f"[{fam}]")
            for name in rules:
                first = docs[name].splitlines()[0] if docs.get(name) else ""
                print(f"  {name}: {first}")
        return 0
    _pin_platform()
    from .audit_targets import REGISTRY
    from .runner import changed_files, run_audit

    if args.list_targets:
        for name, t in REGISTRY.items():
            print(f"{name}: {t.doc}")
            print(f"    sources: {', '.join(t.sources)}")
            for rule, reason in sorted(t.waivers.items()):
                print(f"    waiver[{rule}]: {reason}")
        return 0
    split = (lambda s: [r.strip() for r in s.split(",") if r.strip()])
    if args.mem_table:
        from .mem import format_peak_table, peak_table

        names = split(args.targets) if args.targets else None
        rows = peak_table(names, with_xla=args.mem_xla)
        print(format_peak_table(rows))
        over = [r for r in rows
                if r["hbm_budget"] is None
                or (r["headroom_bytes"] is not None
                    and r["headroom_bytes"] < 0)]
        return 1 if over else 0
    try:
        changed = None
        if args.changed is not None:
            changed = changed_files(args.changed)
        result = run_audit(
            select=split(args.select) if args.select else None,
            ignore=split(args.ignore) if args.ignore else None,
            targets=split(args.targets) if args.targets else None,
            changed=changed,
        )
    except ValueError as e:
        print(f"graftaudit: error: {e}", file=sys.stderr)
        return 2
    if args.sarif:
        from ..sarif import build_sarif_doc

        doc = json.dumps(build_sarif_doc(
            "graftaudit", rule_docs(), family_of,
            result.findings, result.suppressed,
        ), indent=1)
        if args.sarif == "-":
            print(doc)
        else:
            with open(args.sarif, "w", encoding="utf-8") as fh:
                fh.write(doc + "\n")
    if args.as_json:
        print(json.dumps(result.to_dict(), indent=1))
        return result.exit_code
    for f in result.findings:
        print(f"{f.path}:{f.line}:{f.col + 1}: {f.rule}: "
              f"[{family_of(f.rule)}] {f.message}")
    changed_note = ""
    if changed is not None:
        changed_note = f" [--changed: {len(changed)} changed file(s)]"
    print(
        f"graftaudit: {len(result.findings)} finding(s) "
        f"({len(result.suppressed)} waived) across "
        f"{len(result.targets)} program(s){changed_note}"
    )
    return result.exit_code
