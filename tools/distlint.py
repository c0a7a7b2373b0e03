#!/usr/bin/env python
"""distlint CLI: lint the repo's step functions and comm protocols.

    python tools/distlint.py --all              # every registered family
    python tools/distlint.py --family lm        # one family
    python tools/distlint.py --family sgd --family ea
    python tools/distlint.py --list             # what's registered
    python tools/distlint.py --all --disable DL004
    python tools/distlint.py --all --format json
    python tools/distlint.py --model            # protocol model checking
    python tools/distlint.py --races            # lockset race detection
    python tools/distlint.py --update-budgets   # re-baseline cost lockfiles

Exit code 0 when no error-severity findings survive suppression, 1 when
findings remain, 2 on usage errors.  Rule catalog: docs/LINT.md.

``--update-budgets`` compiles every selected family, rewrites its budget
lockfile (``distlearn_tpu/lint/budgets/<family>.json``) from the fresh
numbers, and exits 0 — commit the diff alongside the change that moved
the traffic.  ``--format json`` emits machine-readable findings plus the
per-family cost tables (bytes per collective kind per mesh axis, op
counts, peak memory).
"""

import argparse
import json
import os
import sys

# The step families need a multi-device mesh; force 8 virtual CPU devices
# BEFORE jax initialises (tier-1 runs the same way via tests/conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Match tests/conftest.py: the budget lockfiles carry byte counts, and x64
# widens integer temporaries — the CLI and the tier-1 gate must compile the
# exact same programs or the two contexts would disagree on the budgets.
jax.config.update("jax_enable_x64", True)

from distlearn_tpu.lint.core import RULES, format_findings  # noqa: E402
from distlearn_tpu.lint import budget as budget_mod  # noqa: E402
from distlearn_tpu.lint import registry  # noqa: E402


def _cost_table(reports) -> dict:
    return {name: rep.to_json() for name, rep in sorted(reports.items())}


def _print_cost_table(family: str, reports) -> None:
    for name, rep in sorted(reports.items()):
        ops = rep.ops_by_axis
        parts = [f"{k}: {v}B/{ops[k]}op"
                 for k, v in sorted(rep.bytes_by_axis.items())]
        peak = rep.peak_bytes
        parts.append(f"peak: {peak}B" if peak is not None else "peak: n/a")
        if rep.relayout_ops is not None:
            parts.append(f"entry relayouts: {rep.relayout_ops}")
        print(f"  {family}:{name:24s} " + ("; ".join(parts) or "no traffic"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="distlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--all", action="store_true",
                    help="lint every registered family")
    ap.add_argument("--family", action="append", default=[],
                    metavar="NAME", help="lint one family (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="list registered families and rules, then exit")
    ap.add_argument("--model", action="store_true",
                    help="run the explicit-state protocol models + "
                         "schedule conformance (shorthand for "
                         "--family model)")
    ap.add_argument("--races", action="store_true",
                    help="run the static lockset race detector "
                         "(shorthand for --family races)")
    ap.add_argument("--disable", action="append", default=[],
                    metavar="RULE", help="suppress a rule id (repeatable)")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="output format (json: findings + cost tables)")
    ap.add_argument("--update-budgets", action="store_true",
                    help="rewrite the selected families' cost budget "
                         "lockfiles from a fresh compile (then commit them)")
    ap.add_argument("--budget-dir", default=None, metavar="DIR",
                    help="override the lockfile directory "
                         "(default: distlearn_tpu/lint/budgets)")
    ap.add_argument("--costs", action="store_true",
                    help="print the per-unit cost tables with text output")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="print findings only, no per-unit OK lines")
    args = ap.parse_args(argv)

    fams = registry.families()
    if args.list:
        print("families:")
        for name, e in fams.items():
            print(f"  {name:10s} {e.description}")
        print("rules:")
        for rid, (title, sev) in RULES.items():
            print(f"  {rid}  [{sev}] {title}")
        return 0

    if args.model:
        args.family.append("model")
    if args.races:
        args.family.append("races")
    wanted = list(fams) if (args.all or (args.update_budgets
                                         and not args.family)) \
        else args.family
    if not wanted:
        ap.print_usage(sys.stderr)
        print("distlint: pass --all, --family NAME, or --list",
              file=sys.stderr)
        return 2
    unknown = [f for f in wanted if f not in fams]
    if unknown:
        print(f"distlint: unknown family {unknown} "
              f"(have: {', '.join(fams)})", file=sys.stderr)
        return 2

    if args.update_budgets:
        for fam in wanted:
            _, reports = registry.run_family_costed(
                fam, budget_dir=args.budget_dir)
            path = budget_mod.save_budget(fam, reports,
                                          budget_dir=args.budget_dir)
            print(f"distlint: wrote {path} ({len(reports)} unit(s))")
        return 0

    try:
        suppress = set(args.disable)
        results = []
        all_reports = {}
        for fam in wanted:
            res, reports = registry.run_family_costed(
                fam, suppress=suppress, budget_dir=args.budget_dir)
            results += res
            all_reports[fam] = reports
    except ValueError as e:   # unknown rule id in --disable
        print(f"distlint: {e}", file=sys.stderr)
        return 2

    bad = sum(0 if r.ok else 1 for r in results)
    total = sum(len(r.findings) for r in results)

    if args.format == "json":
        doc = {
            "findings": [
                {"unit": r.name, "rule": f.rule, "severity": f.severity,
                 "where": f.where, "message": f.message}
                for r in results for f in r.findings],
            "costs": {fam: _cost_table(reports)
                      for fam, reports in all_reports.items()},
            "compiles": {r.name.split(":", 1)[0]: r.info
                         for r in results
                         if r.name.endswith(":compiles") and r.info},
            "rules": sorted(RULES),
            "info": {r.name: r.info for r in results if r.info},
            "units": len(results),
            "errors": bad,
        }
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 1 if bad else 0

    for res in results:
        if res.findings:
            print(format_findings(res.findings, header=f"{res.name}:"))
        elif not args.quiet:
            extra = ""
            if "states" in res.info:
                extra = f" ({res.info['states']:,} states)"
            elif "count" in res.info:
                extra = (f" ({res.info['count']} compile(s), "
                         f"~{res.info['warmup_s_estimate']}s warmup)")
            print(f"{res.name}: OK{extra}")
    if args.costs:
        print("costs (bytes/step per device, post-fusion):")
        for fam, reports in all_reports.items():
            _print_cost_table(fam, reports)
    print(f"distlint: {len(results)} unit(s), {total} finding(s)"
          + (f", {bad} with errors" if bad else ""))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
