"""Loop driver ``train_window``: call the family's training program again and
again until ``--seconds`` have passed, ending at a call boundary."""

from __future__ import annotations

import sys
import time
from typing import Any, Dict

import common
import tracing
from common import Checks


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devs) -> Dict[str, Any]:
    cfg, mix = cell["cfg"], cell["mix"]
    family = common.module("families", cfg["family"])
    prog = family.TrainProgram(cfg, mix, seed)
    readings = prog.first_steps()          # compiles; through the window's call
    for _ in range(mix.get("warm_calls", 3)):
        prog.call()
    checks = Checks()

    call_s = []
    tracer = tracing.DeviceTrace(trace, mix.get("trace_seconds", 3))
    setup_s = time.perf_counter() - common.START
    t0 = time.perf_counter()
    t = t0
    while t - t0 < seconds:
        if tracer.maybe_start(t - t0, seconds):
            t = time.perf_counter()        # the profiler's start is no call
        with tracer.annotate("bench_train_call"):
            prog.call()                    # ends with the losses on the host
        now = time.perf_counter()
        call_s.append(now - t)
        t = now
    wall = t - t0 - tracer.overhead_s
    tracer.stop()
    calls = len(call_s)
    rate = calls * prog.samples_per_call / wall
    # where a run reads far off, this says whether one call stalled or all
    # were slow
    mid = common.median(call_s)
    print("train call ms: " + " ".join(
        f"p{q}={1e3 * common.percentile(call_s, q):.1f}" for q in (50, 99, 100))
        + f"; {sum(c > 2 * mid for c in call_s)} of {calls} calls over twice "
        f"the median", file=sys.stderr)

    device = common.device_record(devs)
    device["memory_peak_bytes"] = common.memory_peak_bytes(devs)
    values = {"train_samples_per_s": rate, "setup_s": setup_s}
    ctx = {"cell": cell, "kind": devs[0].device_kind, "call_s": call_s,
           "steps_per_call": prog.steps_per_call,
           "samples_per_call": prog.samples_per_call, "rate": rate,
           "dispatch": prog.dispatch_counts(), "spans": [],
           "trace": None, "chips": cell["chips"]}
    prog.free()
    family.verify(cfg, mix, seed, readings, checks)

    out = {"checks": checks, "attempted": calls * prog.steps_per_call,
           "failed": 0, "device": device, "values": values, "ctx": ctx}
    if trace:
        ctx["trace"] = tracer.reduced(gap_default="between_calls")
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    return out
