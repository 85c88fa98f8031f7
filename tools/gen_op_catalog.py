#!/usr/bin/env python
"""Generate docs/OP_CATALOG.md from the live op registry.

The codegen role (contrib/codegen-tools in the reference): there, a Kotlin
DSL generates the SDOps factories and op classes from op definitions. Here
the ops are plain Python in one registry, so what codegen buys is the
always-current CATALOG REFERENCE — this tool walks the 270-entry registry
plus the SameDiff graph-op table and emits a markdown inventory (name,
signature, first doc line, platform-helper overrides). Run after adding
ops; tests/test_op_validation.py separately enforces per-op validation
coverage.

    python tools/gen_op_catalog.py          # rewrite docs/OP_CATALOG.md
    python tools/gen_op_catalog.py --check  # fail if out of date (CI role)
"""

from __future__ import annotations

import inspect
import io
import re
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def generate() -> str:
    import deeplearning4j_tpu  # noqa: F401 — triggers registrations
    from deeplearning4j_tpu.autodiff.samediff import GRAPH_OPS
    from deeplearning4j_tpu.ops.registry import registry

    reg = registry()
    out = io.StringIO()
    out.write(
        "# Declarable-op catalog\n\n"
        "GENERATED by `tools/gen_op_catalog.py` — do not edit by hand.\n\n"
        "The named-op compat surface (libnd4j `OpRegistrator` role): every\n"
        "op is callable via `ops.registry.exec_op(name, ...)` and\n"
        "recordable in a SameDiff graph via `sd.op(name, ...)`.\n")

    names = reg.names()
    out.write(f"\n## Registry ops ({len(names)})\n\n")
    out.write("| op | signature | summary | platform overrides |\n")
    out.write("|---|---|---|---|\n")
    for name in names:
        desc = reg.get(name)
        try:
            sig = str(inspect.signature(desc.fn))
        except (TypeError, ValueError):
            sig = "(…)"
        # callable defaults repr with memory addresses — stabilize. The
        # repr may nest brackets ('<function <lambda> at 0x…>',
        # '<PjitFunction of <function tanh at 0x…>>'), so strip innermost-
        # first until no address remains.
        while " at 0x" in sig:
            new = re.sub(r"<[^<>]*? at 0x[0-9a-f]+>", "<fn>", sig)
            if new == sig:  # malformed repr: drop the raw address itself
                new = re.sub(r" at 0x[0-9a-f]+", "", sig)
            sig = new
        doc = (desc.doc or "").strip().splitlines()
        summary = doc[0].strip() if doc else ""
        plats = ", ".join(sorted(desc.platform_impls)) or "—"
        sig = sig.replace("|", "\\|")
        summary = summary.replace("|", "\\|")
        out.write(f"| `{name}` | `{sig}` | {summary} | {plats} |\n")

    graph_only = sorted(n for n in GRAPH_OPS if n not in reg)
    out.write(f"\n## Graph-dialect ops ({len(graph_only)})\n\n")
    out.write("Recordable via `sd.op`/namespaces but not in the declarable "
              "registry (shape/control-flow sugar the reference keeps on "
              "the SameDiff side).\n\n")
    for name in graph_only:
        out.write(f"- `{name}`\n")
    return out.getvalue()


def main() -> int:
    path = os.path.join(REPO, "docs", "OP_CATALOG.md")
    text = generate()
    if "--check" in sys.argv:
        if not os.path.exists(path) or open(path).read() != text:
            print("OP_CATALOG.md is out of date — run tools/gen_op_catalog.py")
            return 1
        print("OP_CATALOG.md up to date")
        return 0
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    print(f"wrote {path} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
