"""The longest of the window's ``fit_scanned`` spans, in ms: where one call
in some hundreds stalls for seconds (a stall: over ten times the median),
this is its length, and stderr names what the host was doing in it. The
window's calls are the program's last ``len(ctx["call_s"])`` ``fit_scanned``
spans, read from the program's tracer (the loop hands its readers none).

On stderr, for that call: its dispatch and its read, each with ``cpu_ms``
and ``gc_ms``, and the ``host_gc`` (with the other collector callbacks'
time around the pass) and ``xla_compile`` spans that share time with it on
any thread; the five longest calls with their places in the window; every
generation-2 pass of the window; and the longest call of the last
``trace_seconds`` of calls (the traced seconds) beside the run's device idle
share."""

import sys

import common

host = common.module("layer_metrics", "host_accounting")
spans_of = host.spans_of


def read(ctx):
    n = len(ctx.get("call_s") or ())
    if not n or spans_of.dropped():
        return None
    spans = spans_of.program_spans()
    calls = host.accounted(spans, "fit_scanned")
    if calls is None or len(calls) < n:
        return None
    calls = calls[-n:]
    kids = spans_of.children_of(spans)
    worst = max(calls, key=lambda c: c["seconds"])
    median = common.median([c["seconds"] for c in calls])
    stages = sorted(kids.get(worst["args"]["id"], []),
                    key=lambda s: s["start"])
    others = host.overlapping(spans, worst, ("host_gc", "xla_compile"))
    print(f"longest train call: call={worst['args'].get('call')} "
          f"{spans_of.ms(worst['seconds'])} ms, "
          f"{worst['seconds'] / median:.1f}x the median "
          f"{spans_of.ms(median)} ms; {host.usage(worst)}", file=sys.stderr)
    print("  its stages: " + "; ".join(host.described(s) for s in stages),
          file=sys.stderr)
    print("  beside it, on any thread: "
          + ("; ".join(host.described(s) for s in others) or "no host_gc, "
             "no xla_compile"), file=sys.stderr)
    order = {id(c): i for i, c in enumerate(calls)}
    longest = sorted(calls, key=lambda c: -c["seconds"])[:5]
    print("  five longest calls (ms @ call of the window): " + ", ".join(
        f"{spans_of.ms(c['seconds'])} @ {order[id(c)] + 1}" for c in longest)
        + f" of {n}; {sum(c['seconds'] > 10 * median for c in calls)} over "
        f"ten times the median", file=sys.stderr)
    t0, t1 = calls[0]["start"], calls[-1]["start"] + calls[-1]["seconds"]
    full = [s for s in spans if s["name"] == "host_gc"
            and s["args"].get("generation") == 2 and t0 <= s["start"] <= t1]
    print("  full passes in the window: " + ("; ".join(
        host.described(s) for s in full) or "none"), file=sys.stderr)
    length = float((ctx.get("cell") or {}).get("mix", {}).get(
        "trace_seconds", 3))
    traced = host.inside(calls, (t1 - length, t1))
    if traced and ctx.get("trace"):
        top = max(traced, key=lambda c: c["seconds"])
        print(f"  the last {length:g} s of calls (the traced seconds): "
              f"longest {spans_of.ms(top['seconds'])} ms of {len(traced)}; "
              f"{host.idle_note(ctx, 'train')}", file=sys.stderr)
    return 1e3 * worst["seconds"]
