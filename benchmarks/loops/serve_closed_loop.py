"""Loop driver ``serve_closed_loop``: ``clients`` callers, each submitting
its next request when the last returns. One driver thread holds every
caller's future. The callers start one after another during the set-up's
ramp, so the window opens on a full bank and not on a burst."""

from __future__ import annotations

import gc
import sys
import time
from concurrent.futures import FIRST_COMPLETED, wait
from typing import Any, Dict, List

import numpy as np

import common
import tracing
import traffic_gen
from common import Checks


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devs) -> Dict[str, Any]:
    cfg, mix = cell["cfg"], cell["mix"]
    family = common.module("families", cfg["family"])
    hands = traffic_gen.client_sequences(mix, seed, cfg["vocab_size"])
    prog = family.ServeProgram(cfg, mix, seed)
    checks = Checks()
    records: List[Dict[str, Any]] = []
    pending: Dict[Any, Dict[str, Any]] = {}
    next_of = [0] * len(hands)

    def submit(client: int) -> None:
        hand = hands[client]
        request = hand[next_of[client] % len(hand)]
        next_of[client] += 1
        rec = {"client": client, "request": request,
               "submit": time.perf_counter(), "result": None}
        pending[prog.submit(request)] = rec
        records.append(rec)

    def reap(timeout: float, resubmit: bool) -> None:
        if not pending:
            time.sleep(min(timeout, 0.01))
            return
        done, _ = wait(list(pending), timeout=timeout,
                       return_when=FIRST_COMPLETED)
        for fut in done:
            rec = pending.pop(fut)
            try:
                rec["result"] = fut.result()
            except Exception as e:  # a failed request is counted, not raised
                rec["error"] = repr(e)
            rec["done"] = time.perf_counter()
            if resubmit:
                submit(rec["client"])

    # warm-up: the first request alone pays for the compiles
    warm = dict(hands[0][0], max_new_tokens=4)
    prog.submit(warm).result(timeout=1100)
    # ramp: callers join one after another; part of the set-up
    ramp = float(mix.get("ramp_seconds", 0))
    r0 = time.perf_counter()
    for c in range(len(hands)):
        while time.perf_counter() - r0 < ramp * c / len(hands):
            reap(0.02, True)
        submit(c)
    while time.perf_counter() - r0 < ramp:
        reap(0.02, True)

    tracer = tracing.DeviceTrace(trace, mix.get("trace_seconds", 4))
    prog.clear_spans()
    setup_s = time.perf_counter() - common.START
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        tracer.maybe_start(now - t0, seconds)
        reap(min(0.05, max(seconds - (now - t0), 0.001)), True)
    t1 = time.perf_counter()
    tracer.stop()
    # the callers stop; what is in flight is waited for, a minute at most
    deadline = t1 + 60
    while pending and time.perf_counter() < deadline:
        reap(0.5, False)
    spans = prog.spans()
    stopped = prog.stop()
    checks.require("engine_stopped_cleanly", stopped)

    stats = window_stats(records, t0, t1, family)
    print("serve percentiles ms: " + "; ".join(
        f"{k} " + " ".join(f"p{q}={common.percentile(stats[k], q):.1f}"
                           for q in (50, 90, 95, 99))
        for k in ("ttft_ms", "gap_ms") if stats[k]), file=sys.stderr)
    device = common.device_record(devs)
    device["memory_peak_bytes"] = common.memory_peak_bytes(devs)
    values = {"serve_tokens_per_s": stats["tokens"] / (t1 - t0),
              "serve_ttft_p95_ms": stats["ttft_p95_ms"], "setup_s": setup_s}
    ctx = {"cell": cell, "kind": devs[0].device_kind, "chips": cell["chips"],
           "spans": [s for s in spans if t0 <= s["start"] <= t1],
           "window": (t0, t1), "stats": stats,
           # per request that got a first token: (prompt length, when each
           # served token reached the caller)
           "tokens": [(len(r["request"]["prompt"]), token_times(r))
                      for r in records if len(token_times(r))],
           "max_slots": prog.max_slots, "dispatch": prog.dispatch_counts(),
           "trace": None}
    sample = ctx["sample"] = check_sample(records, mix, seed)
    prog.free()
    gc.collect()
    family.verify(cfg, mix, seed, sample, checks)

    out = {"checks": checks, "attempted": stats["attempted"],
           "failed": stats["failed"], "device": device, "values": values,
           "ctx": ctx}
    if trace:
        ctx["trace"] = tracer.reduced(
            gap_default="engine_between_steps",
            spans=[("bench_" + s["name"], s["start"], s["seconds"])
                   for s in spans])
        ctx["traced"] = (tracer.sync_perf,
                         tracer.sync_perf + ctx["trace"]["window_s"])
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    return out


def token_times(rec: Dict[str, Any]) -> np.ndarray:
    """When each served token reached the caller's side, host clock."""
    res = rec["result"]
    if res is None or res.ttft_s is None:
        return np.zeros((0,))
    gaps = np.asarray(res.intertoken_s, float)
    return rec["submit"] + res.ttft_s + np.concatenate([[0.0],
                                                        np.cumsum(gaps)])


def window_stats(records, t0: float, t1: float, family) -> Dict[str, Any]:
    """Over ALL requests: tokens generated inside the window; the wait for
    the first token of every request submitted inside it (a failed one
    counts as the whole window); every gap between tokens that ended
    inside it."""
    tokens, ttft, gaps, attempted, failed = 0, [], [], 0, 0
    for rec in records:
        times = token_times(rec)
        tokens += int(np.sum((times >= t0) & (times <= t1)))
        if len(times) > 1:
            ends = times[1:]
            inside = (ends >= t0) & (ends <= t1)
            gaps.extend((np.diff(times)[inside] * 1e3).tolist())
        if t0 <= rec["submit"] <= t1:
            attempted += 1
            bad = family.request_failed(rec["request"], rec["result"])
            failed += int(bad)
            res = rec["result"]
            ttft.append((t1 - t0) * 1e3 if bad or res.ttft_s is None
                        else res.ttft_s * 1e3)
    return {"tokens": tokens, "attempted": attempted, "failed": failed,
            "requests_ttft": len(ttft), "gaps": len(gaps),
            "ttft_p95_ms": common.percentile(ttft, 95) if ttft else None,
            "itl_p95_ms": common.percentile(gaps, 95) if gaps else None,
            "ttft_ms": ttft, "gap_ms": gaps}


def check_sample(records, mix, seed: int) -> List[Dict[str, Any]]:
    """A sample, drawn from the seed, of the requests the window finished,
    the longest among them."""
    done = [r for r in records
            if r["result"] is not None and len(r["result"].tokens) > 0]
    if not done:
        return []
    size = lambda r: len(r["request"]["prompt"]) + len(r["result"].tokens)  # noqa: E731
    longest = max(range(len(done)), key=lambda i: size(done[i]))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    k = min(int(mix.get("check_requests", 6)), len(done))
    picks = rng.choice(len(done), size=k, replace=False).tolist()
    if longest not in picks:
        picks[-1] = longest
    return [{"prompt": done[i]["request"]["prompt"],
             "tokens": done[i]["result"].tokens} for i in sorted(picks)]
