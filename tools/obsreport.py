#!/usr/bin/env python
"""obsreport — summarize the runtime telemetry of a run (docs/OBSERVABILITY.md).

Two modes:

* default / ``--json``: run the built-in smoke workload — a small
  MultiLayerNetwork fit (two batch shapes, so the recompile ledger records
  both a ``first_compile`` and a ``new_shape`` event) plus a multithreaded
  ``ParallelInference`` serving burst — then print a human report (or, with
  ``--json``, ONE machine-parsable line: the gate-stage contract, same as
  lint/check). This is the acceptance probe: nonzero step counts, at least
  one recompile event with a cause, serving p50/p99.
* ``--log PATH``: summarize an existing ``DL4J_TPU_OBS_LOG`` JSONL file
  instead of running anything (post-hoc analysis of a training/serving run).

The smoke workload is a CPU correctness gate on the telemetry plumbing
(``make obs-smoke`` pins ``JAX_PLATFORMS=cpu``): its ``--json`` line carries
counts and whether percentiles were produced, never a time. It runs on
whatever platform JAX has and names it; there is no fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter as _Counter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _demo_workload() -> None:
    """Small MLN fit (two feed shapes) + concurrent ParallelInference."""
    import threading

    import numpy as np

    from deeplearning4j_tpu import nn
    from deeplearning4j_tpu.parallel.mesh import ParallelInference

    n_in, n_out = 8, 4
    conf = (nn.builder().seed(0).updater(nn.Adam(learning_rate=1e-2)).list()
            .layer(nn.DenseLayer(n_out=16, activation="relu"))
            .layer(nn.OutputLayer(n_out=n_out, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.feed_forward(n_in)).build())
    net = nn.MultiLayerNetwork(conf).init()
    r = np.random.RandomState(0)
    x = r.randn(64, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[r.randint(0, n_out, 64)]
    net.fit(x, y, epochs=2, batch_size=16)          # first_compile @ b=16
    net.fit(x[:48], y[:48], epochs=1, batch_size=24)  # new_shape @ b=24

    pi = ParallelInference(net, max_batch=8, window_ms=2.0).start()
    errors = []
    try:
        pi.predict(x[0])  # warm the compiled serving path

        def client(seed: int) -> None:
            rr = np.random.RandomState(seed)
            try:
                for _ in range(8):
                    out = pi.predict(rr.randn(n_in).astype(np.float32))
                    assert out.shape[-1] == n_out
            except Exception as e:  # re-raised below: a dead serving path
                errors.append(e)    # must fail the smoke, not pass it
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        pi.stop()
    if errors:
        raise RuntimeError(f"{len(errors)} serving client(s) failed: "
                           f"{errors[0]!r}")


def _fmt_ms(v) -> str:
    return "n/a" if v is None else f"{v:.2f} ms"


def _report(backend: str) -> dict:
    """Assemble the summary dict from the live registry/ledger."""
    from deeplearning4j_tpu import observe

    s = observe.summary()
    events = [ev.to_dict() for ev in observe.ledger().events()]
    return {"backend": backend, "summary": s, "recompile_events": events}


def _print_human(rep: dict) -> None:
    s = rep["summary"]
    print("== dl4j-tpu observability report ==")
    print(f"backend: {rep['backend']}")
    tr = s.get("train")
    if tr:
        print(f"train: {tr['steps']} steps, {tr['examples']} examples; "
              f"step latency p50 {_fmt_ms(tr['step_p50_ms'])}, "
              f"p95 {_fmt_ms(tr['step_p95_ms'])}, "
              f"p99 {_fmt_ms(tr['step_p99_ms'])}")
    rec = s.get("recompiles")
    if rec:
        causes = ", ".join(f"{k}: {v}"
                           for k, v in sorted(rec["by_cause"].items()))
        print(f"recompiles: {rec['total']} total ({causes})")
        for ev in rep["recompile_events"][-10:]:
            extra = ""
            if ev.get("compile_seconds") is not None:
                extra = (f"  trace {ev.get('trace_seconds')}s"
                         f" compile {ev.get('compile_seconds')}s")
            print(f"  [{ev['seq']}] {ev['graph']}/{ev['key']} "
                  f"cause={ev['cause']} sig={ev['signature']}{extra}")
    sv = s.get("serving")
    if sv:
        print(f"serving: {sv['requests']} requests in {sv['batches']} "
              f"batches; latency p50 {_fmt_ms(sv['p50_ms'])}, "
              f"p95 {_fmt_ms(sv['p95_ms'])}, p99 {_fmt_ms(sv['p99_ms'])}; "
              f"batch occupancy mean {sv['batch_occupancy_mean']}")
    disp = s.get("dispatch")
    if disp:
        print("helper dispatch (op/impl/reason):")
        for key, count in disp.items():
            print(f"  {key}: {count}")
    if not (tr or rec or sv or disp):
        print("no telemetry recorded (did the workload run?)")


def _summarize_log(path: str, json_mode: bool) -> int:
    """Post-hoc summary of a DL4J_TPU_OBS_LOG JSONL file."""
    kinds: "_Counter[str]" = _Counter()
    causes: "_Counter[str]" = _Counter()
    fusion_hits: "_Counter[str]" = _Counter()
    train_steps = 0
    serving_rows = 0
    bad = 0
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad += 1
                continue
            kind = rec.get("kind", "?")
            kinds[kind] += 1
            if kind == "recompile":
                causes[rec.get("cause", "?")] += 1
                # fusion-tier hits ride the recompile event (CompileEvent
                # carries the live OptimizeStats.fusions section)
                for fk, fv in (rec.get("fusions") or {}).items():
                    fusion_hits[fk] += int(fv)
            elif kind == "train_epoch":
                train_steps += int(rec.get("steps", 0))
            elif kind == "serving_batch":
                serving_rows += int(rec.get("rows", 0))
    out = {"tool": "obsreport", "log": path, "events": sum(kinds.values()),
           "by_kind": dict(kinds), "recompile_causes": dict(causes),
           "fusion_hits": dict(fusion_hits),
           "train_steps": train_steps, "serving_rows": serving_rows,
           "unparsable_lines": bad}
    if json_mode:
        print(json.dumps(out, sort_keys=True))
    else:
        print(f"== obs log summary: {path} ==")
        for k, v in sorted(kinds.items()):
            print(f"  {k}: {v}")
        if causes:
            print("  recompile causes: "
                  + ", ".join(f"{k}={v}" for k, v in sorted(causes.items())))
        if fusion_hits:
            print("  fusion hits: "
                  + ", ".join(f"{k}={v}"
                              for k, v in sorted(fusion_hits.items())))
        print(f"  train steps: {train_steps}; serving rows: {serving_rows}")
        if bad:
            print(f"  WARNING: {bad} unparsable lines")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="one machine-parsable JSON line (gate contract)")
    ap.add_argument("--log", metavar="PATH",
                    help="summarize an existing DL4J_TPU_OBS_LOG JSONL file "
                         "instead of running the smoke workload")
    args = ap.parse_args()

    if args.log:
        return _summarize_log(args.log, args.json)

    import jax

    backend = jax.devices()[0].platform
    _demo_workload()
    rep = _report(backend)

    if args.json:
        s = rep["summary"]
        tr = s.get("train") or {}
        sv = s.get("serving") or {}
        rec = s.get("recompiles") or {}
        line = {"tool": "obsreport", "backend": backend,
                "train_steps": tr.get("steps", 0),
                "recompiles": rec.get("total", 0),
                "recompile_causes": rec.get("by_cause", {}),
                "serving_requests": sv.get("requests", 0),
                "serving_percentiles": sv.get("p99_ms") is not None}
        ok = (line["train_steps"] > 0 and line["recompiles"] > 0
              and line["serving_requests"] > 0
              and line["serving_percentiles"])
        line["ok"] = ok
        print(json.dumps(line, sort_keys=True))
        return 0 if ok else 1
    _print_human(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
