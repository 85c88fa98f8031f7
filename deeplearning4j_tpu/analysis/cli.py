"""graftcheck CLI — the gate's ``check`` stage.

    python -m deeplearning4j_tpu.analysis [options]
    python tools/graftcheck.py              # identical thin wrapper

Runs the abstract shape/dtype interpreter over the fixture zoo
(``analysis/fixtures.py``: the examples' SameDiff graphs, symbolic-batch
CNN/BERT encoders, a numpy-static shape chain, an ONNX-dialect import,
and zoo networks) and diffs the findings against the committed
shrink-only ``check_baseline.json``.

Options:
    --baseline PATH    baseline file (default: <repo>/check_baseline.json)
    --write-baseline   regenerate the baseline (shrink-only; new findings
                       are REFUSED and exit 1 — see --allow-growth)
    --allow-growth     allow --write-baseline to add new keys (onboarding)
    --json             emit exactly ONE machine-readable JSON summary line
                       (the tools/gate.py driver-artifact contract)
    --list-codes       print the GC code catalog and exit

Exit code 0 iff there are no findings beyond the grandfathered baseline.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

from deeplearning4j_tpu.lint.core import Finding, run_baselined_cli

_CHECK_BASELINE_COMMENT = (
    "graftcheck grandfathered findings — every entry is debt; shrink, "
    "never grow. Regenerate: make check-baseline")


def find_repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def collect_findings() -> List[Finding]:
    """Check every clean fixture; any finding at all is reportable (the
    committed baseline is empty — the fixtures must stay clean)."""
    from deeplearning4j_tpu.analysis import check_network, check_samediff
    from deeplearning4j_tpu.analysis import fixtures
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    findings: List[Finding] = []
    for name, graph in fixtures.clean_fixtures():
        if isinstance(graph, SameDiff):
            report = check_samediff(graph, graph_name=name)
        else:
            report = check_network(graph, graph_name=name)
        findings.extend(report.findings)
    return sorted(findings)


def run(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="graftcheck", description=__doc__)
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--allow-growth", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--list-codes", action="store_true")
    args = ap.parse_args(argv)

    if args.list_codes:
        from deeplearning4j_tpu.analysis.report import GC_CODES
        for code, (severity, title) in sorted(GC_CODES.items()):
            print(f"{code}  {severity:7s}  {title}")
        return 0

    # pin the CPU backend before any fixture touches the registries: the
    # check stage is a CPU tool and must never take the chip
    os.environ["JAX_PLATFORMS"] = "cpu"

    baseline_path = args.baseline or os.path.join(find_repo_root(),
                                                  "check_baseline.json")
    findings = collect_findings()

    # shared baseline-CLI tail (lint/core.py — also drives graftlint)
    return run_baselined_cli(
        "graftcheck", findings, baseline_path,
        write=args.write_baseline, allow_growth=args.allow_growth,
        json_mode=args.json, comment=_CHECK_BASELINE_COMMENT,
        fail_hint="an op rule, importer, or fixture regressed; see "
                  "docs/ANALYSIS.md")


def main() -> None:
    sys.exit(run())
