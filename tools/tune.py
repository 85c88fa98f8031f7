#!/usr/bin/env python
"""Kernel autotuner CLI (docs/KERNELS.md) — measure, persist, verify.

    python tools/tune.py --smoke --json     # make tune-smoke / gate stage
    python tools/tune.py                    # full ladders (run on-chip)
    python tools/tune.py --ops dot_product_attention,matmul_int8

Runs ``ops.tuning.autotune`` (AOT-timed candidates, nothing enters the jit
cache), writes the measured table to ``DL4J_TPU_TUNING_DIR`` (or, with
that unset, to the git-ignored ``.tuning/`` of the checkout, which dispatch
does not read — commit a copy under ``ops/tuning_tables/`` to make it
live), then VERIFIES that the live threshold steers dispatch: reloads
the tables, resolves ``dot_product_attention`` on both sides of the live
``flash_min_t`` under forced-pallas mode, and asserts via the
``dl4j_tpu_helper_dispatch_total`` counters that the small shape dispatched
to the XLA generic and the large shape to the Pallas helper. One JSON line
(``"tool": "tune"``) on stdout is the machine contract; exit 0 iff the
table saved and the dispatch proof held.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _verify_dispatch() -> dict:
    """Prove the tuned threshold steers resolve, via the dispatch counters."""
    import jax.numpy as jnp

    import deeplearning4j_tpu.ops  # registers the catalog + helpers
    from deeplearning4j_tpu import observe
    from deeplearning4j_tpu.environment import environment
    from deeplearning4j_tpu.ops import tuning
    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_min_t, reset_flash_min_t_cache)
    from deeplearning4j_tpu.ops.registry import registry

    tuning.reset_tables()  # pick up the table autotune just saved
    reset_flash_min_t_cache()
    threshold = flash_min_t()
    desc = registry().get("dot_product_attention")
    env = environment()
    old = env.helper_mode
    env.helper_mode = "pallas"  # force platform-table resolution off-TPU
    before = dict(observe.dispatch_summary())
    try:
        t_lo = max(threshold // 2, 8)
        t_hi = max(2 * threshold, 16)
        lo = jnp.zeros((2, t_lo, 16), jnp.float32)
        hi = jnp.zeros((2, t_hi, 16), jnp.float32)
        below = desc.resolve(lo, lo, lo)
        above = desc.resolve(hi, hi, hi)
    finally:
        env.helper_mode = old
    after = observe.dispatch_summary()
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after
             if after.get(k, 0) != before.get(k, 0)}
    below_xla = below is desc.fn and delta.get(
        "dot_product_attention/generic/not_usable", 0) >= 1
    above_pallas = above is desc.platform_impls.get("tpu") and delta.get(
        "dot_product_attention/tpu/usable", 0) >= 1
    return {"flash_min_t": threshold,
            "below_dispatch": "xla" if below_xla else "FAIL",
            "above_dispatch": "pallas" if above_pallas else "FAIL",
            "counters": delta,
            "ok": bool(below_xla and above_pallas)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape ladders (seconds on CPU; the gate/"
                         "make tune-smoke mode)")
    ap.add_argument("--json", action="store_true",
                    help="one machine-parsable JSON line on stdout")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op subset (default: all tuners)")
    ap.add_argument("--no-save", action="store_true",
                    help="measure only; do not write the cache table")
    args = ap.parse_args()

    from deeplearning4j_tpu.ops import tuning

    ops = args.ops.split(",") if args.ops else None
    table, report = tuning.autotune(ops=ops, smoke=args.smoke,
                                    save=not args.no_save)

    verify = None
    ok = True
    if not args.no_save and (ops is None or "dot_product_attention" in ops):
        verify = _verify_dispatch()
        ok = verify["ok"]

    line = {"tool": "tune", **report.to_dict(), "smoke": args.smoke,
            "ok": ok}
    if verify is not None:
        line["verify"] = verify
    if args.json:
        print(json.dumps(line, sort_keys=True))
    else:
        print(f"device kind: {report.device_kind}")
        print(f"tuned ops:   {', '.join(report.ops)}")
        print(f"measured:    {report.measurements} candidates in "
              f"{report.seconds}s")
        if report.table_path:
            print(f"table:       {report.table_path}")
        if verify is not None:
            print(f"dispatch:    below->{verify['below_dispatch']} "
                  f"above->{verify['above_dispatch']} "
                  f"(flash_min_t={verify['flash_min_t']})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
