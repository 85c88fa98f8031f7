"""graftlint CLI.

    python -m deeplearning4j_tpu.lint [paths...] [options]
    python tools/graftlint.py          # identical thin wrapper

Options:
    --baseline PATH    baseline file (default: <repo>/lint_baseline.json)
    --write-baseline   regenerate the baseline from the current findings
                       (shrink-only: findings not already grandfathered are
                       REFUSED and exit 1 — see --allow-growth)
    --allow-growth     allow --write-baseline to add new keys/counts (only
                       for onboarding a brand-new rule)
    --json             emit exactly ONE machine-readable JSON summary line
                       (the driver-artifact contract tools/gate.py relies on)
    --no-consistency   AST rules only (skip registry-loading rules — for
                       environments without jax)
    --rules CSV        run only the named AST rules (e.g. GS001,GS002 —
                       `make shape-lint` uses this to run the graftshape
                       tier alone); implies --no-consistency unless a
                       consistency rule id is in the list
    --list-rules       print the rule catalog and exit

Exit code 0 iff there are no findings beyond the grandfathered baseline.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

from deeplearning4j_tpu.lint.core import (
    AST_RULES, Finding, lint_paths, run_baselined_cli)

DEFAULT_ROOTS = ("deeplearning4j_tpu", "tools", "examples")


def find_repo_root(start: Optional[str] = None) -> str:
    """Walk up from this file to the directory holding the package — the
    lint paths and baseline are repo-relative."""
    here = start or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return here


def run(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="graftlint", description=__doc__)
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--allow-growth", action="store_true",
                    help="let --write-baseline add NEW keys/counts (only "
                         "for onboarding a brand-new rule; the default "
                         "refuses growth so regenerating can never "
                         "grandfather a regression)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--no-consistency", action="store_true")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    subset = bool(args.paths)
    if subset and args.write_baseline and not args.baseline:
        # a subset scan misses every baselined finding outside the subset;
        # writing it over the repo-wide baseline would make the next full
        # run report all of those as NEW
        ap.error("--write-baseline with explicit paths would overwrite the "
                 "repo-wide baseline with a subset scan; pass --baseline "
                 "to write elsewhere or drop the path arguments")

    if args.list_rules:
        from deeplearning4j_tpu.lint.rules_consistency import CONSISTENCY_RULES
        for rid, (_fn, desc) in sorted({**AST_RULES, **CONSISTENCY_RULES}.items()):
            print(f"{rid}  {desc}")
        return 0

    repo_root = find_repo_root()
    roots = list(args.paths) if args.paths else list(DEFAULT_ROOTS)
    baseline_path = args.baseline or os.path.join(repo_root,
                                                  "lint_baseline.json")

    rule_filter = None
    if args.rules:
        rule_filter = tuple(r.strip() for r in args.rules.split(",")
                            if r.strip())
        unknown = [r for r in rule_filter if r not in AST_RULES]
        try:
            from deeplearning4j_tpu.lint.rules_consistency import (
                CONSISTENCY_RULES)
            unknown = [r for r in unknown if r not in CONSISTENCY_RULES]
        except ImportError:
            pass
        if unknown:
            ap.error(f"unknown rule id(s): {', '.join(unknown)} "
                     "(see --list-rules)")

    findings: List[Finding] = lint_paths(
        roots, repo_root,
        rules=[r for r in rule_filter if r in AST_RULES]
        if rule_filter else None)
    if rule_filter is not None:
        # a rule-filtered scan cannot see the other rules' findings, so the
        # consistency tier only runs when one of ITS ids was asked for
        run_cons = (not args.no_consistency and any(
            r not in AST_RULES for r in rule_filter))
    else:
        run_cons = not args.no_consistency
    if run_cons:
        # the consistency rules load the live registries (and thus jax);
        # lint is a CPU tool and must never take the chip from a process
        # that needs it
        os.environ["JAX_PLATFORMS"] = "cpu"
        from deeplearning4j_tpu.lint.rules_consistency import run_consistency
        cons = run_consistency(repo_root)
        if rule_filter is not None:
            cons = [f for f in cons if f.rule in rule_filter]
        findings.extend(cons)
    findings.sort()

    # shared baseline-CLI tail (lint/core.py — also drives graftcheck):
    # --write-baseline shrink-only flow, or diff + one-JSON-line contract
    return run_baselined_cli(
        "graftlint", findings, baseline_path,
        write=args.write_baseline, allow_growth=args.allow_growth,
        json_mode=args.json,
        # a subset scan cannot tell "fixed" from "outside the paths", and a
        # rule-filtered scan cannot tell "fixed" from "rule not run"
        suppress_fixed=subset or rule_filter is not None,
        fail_hint="fix the new findings above or (only with a written "
                  "justification) add a 'graftlint: disable=<RULE>' "
                  "comment")


def main() -> None:
    sys.exit(run())
