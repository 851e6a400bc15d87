"""CLI: ``python -m tools.tpulint mxnet_tpu tools --strict``.

Exit codes: 0 clean (or findings without --strict), 1 findings under
--strict, 2 usage error. The ci/run.sh gate runs --strict; the
fix-or-allowlist workflow is: run, read findings, either fix the code or
add ``# tpulint: disable=<rule> (reason)`` on the flagged line.
"""
from __future__ import annotations

import argparse
import sys

from . import RULES, lint_paths
from .rules import check_env_registry  # noqa: F401 — part of the rule set


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpulint",
        description="framework-invariant static analysis for mxnet_tpu")
    ap.add_argument("paths", nargs="*", default=[],
                    help="files or directories to lint")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any finding survives (the CI gate)")
    ap.add_argument("--env-doc", default="docs/faq/env_var.md",
                    help="env-var doc table for the env-var-registry rule "
                         "(pass 'none' to skip the rule)")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule names to run (default all)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name in sorted(RULES) + ["env-var-registry"]:
            print(name)
        return 0
    if not args.paths:
        ap.error("no paths given")

    select = None if args.select is None \
        else {s.strip() for s in args.select.split(",") if s.strip()}
    if select is not None:
        # a typo'd rule name must NOT produce a vacuous 'clean' exit 0
        known = set(RULES) | {"env-var-registry"}
        unknown = select - known
        if unknown:
            ap.error(f"unknown rule(s): {', '.join(sorted(unknown))} "
                     f"(known: {', '.join(sorted(known))})")
    env_doc = None if args.env_doc == "none" else args.env_doc
    try:
        findings = lint_paths(args.paths, env_doc=env_doc, select=select)
    except FileNotFoundError as e:
        ap.error(f"no such path: {e}")

    for f in findings:
        print(f)
    by_rule = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    if findings:
        summary = ", ".join(f"{n} {r}" for r, n in sorted(by_rule.items()))
        print(f"\ntpulint: {len(findings)} finding(s): {summary}")
        print("fix the code or add '# tpulint: disable=<rule> (reason)' "
              "on the flagged line — the reason is required")
        return 1 if args.strict else 0
    print("tpulint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
