#!/usr/bin/env python3
"""One cell of the benchmark, one process:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds a TPU or fails (no CPU fallback), keeps JAX's compilation cache inside
the checkout, makes weights and inputs from the seed, warms the cell's own
shapes, measures for ``--seconds``, compares what the timed path produced
with the plain reference, and prints the result as the last line of stdout.
The cell's configuration, traffic mix, loop driver and per-layer metrics are
files found by the names in BENCHMARK.json: see benchmarks/README.md.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402  (takes the process's start time)


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = common.load_cell(args.workload)
        try:
            import deeplearning4j_tpu  # noqa: F401
        except ImportError as e:
            raise common.BenchError(f"the program is not in this "
                                    f"directory: {e}") from None
        devs = common.find_chips(cell["chips"], require_tpu=require_tpu)
        common.enable_compile_cache()
        return run_cell(cell, devs, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace))
    except common.BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2


def run_cell(cell, devs, *, seed: int, seconds: float, trace: bool,
             out=sys.stdout, err=sys.stderr) -> int:
    """Everything after the look for a chip: drive the cell's loop, read the
    metrics, print the result line."""
    loop = common.module("loops", cell["mix"]["loop"])
    res = loop.run(cell, seed=seed, seconds=seconds, trace=trace, devs=devs)
    if trace:
        values = common.read_layer_metrics(cell, res["ctx"])
        metrics = common.select_metrics(cell["per_layer"], values)
        tr = res["ctx"]["trace"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    else:
        metrics = common.select_metrics(cell["end_to_end"], res["values"])
        breakdown = None
    common.emit(checks=res["checks"], attempted=res["attempted"],
                failed=res["failed"], metrics=metrics, device=res["device"],
                breakdown=breakdown, out=out, err=err)
    return 0


if __name__ == "__main__":
    sys.exit(main())
