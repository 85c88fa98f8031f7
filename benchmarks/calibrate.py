#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, at the cell's own
size, in one process:

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 --controls 3 --seconds 8

For each seed a short run of the cell with every limit lifted gives the
program's numbers (the lower readings). On the first ``--controls`` seeds
each of the family's stand-ins (the controls and the faults, in the
program's place) then goes through the family's ``verify`` with the limits
AS COMMITTED: its numbers are the upper readings, and ``correct`` has to come
out false for every one of them, or this exits with 1. One JSON line per
seed goes to stdout and to chiprun_out/calibrate_<workload>.jsonl. Not part
of a benchmark run. A family's ``timing(cell, seed, seconds)``, where it has
one, is run by ``--timing`` instead: side measurements for PERF.md."""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402


def main(argv=None, *, require_tpu=True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--timing", action="store_true")
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    devs = common.find_chips(cell["chips"], require_tpu=require_tpu)
    common.enable_compile_cache()
    family = common.module("families", cell["cfg"]["family"])
    if args.timing:
        print(json.dumps(family.timing(cell, args.first_seed, args.seconds),
                         default=float), flush=True)
        return 0
    committed = cell["mix"].get("limits") or cell["cfg"]["limits"]
    lifted = dict(cell, cfg=dict(cell["cfg"],
                                 limits={k: float("inf") for k in committed}),
                  mix={k: v for k, v in cell["mix"].items() if k != "limits"})
    if "ramp_seconds" in lifted["mix"]:
        lifted["mix"]["ramp_seconds"] = min(lifted["mix"]["ramp_seconds"], 3)
    loop = common.module("loops", cell["mix"]["loop"])
    os.makedirs(os.path.join(common.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(common.ROOT, "chiprun_out",
                        f"calibrate_{args.workload}.jsonl")
    passed = []
    with open(path, "a", encoding="utf-8") as log:
        for i in range(args.seeds):
            # spread the seeds, some past 2**31
            seed = args.first_seed + i * 178956971
            out = loop.run(lifted, seed=seed, seconds=args.seconds,
                           trace=False, devs=devs)
            row = {"workload": args.workload, "seed": seed,
                   "program": out["checks"].compared(),
                   "notes": [r.get("note") for r in out["checks"].rows
                             if r.get("note")],
                   "values": out["values"]}
            if i < args.controls:
                row["stand_ins"] = {}
                for tag, readings, kw in family.stand_ins(
                        cell["cfg"], cell["mix"], seed, out["ctx"]):
                    checks = common.Checks()
                    family.verify(cell["cfg"], cell["mix"], seed, readings,
                                  checks, **kw)
                    row["stand_ins"][tag] = {
                        "correct": checks.correct,
                        "compared": checks.compared()}
                    if checks.correct:
                        passed.append((seed, tag))
            line = json.dumps(row, default=float)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    for seed, tag in passed:
        print(f"calibrate: {tag} came out CORRECT on seed {seed}",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
