"""Median ``fit_scanned_dispatch`` (key split, uploads, the jitted call up
to its return), in ms, over the window's calls of the scanned trainer. The
loop hands its readers no spans, so they are read from the program's tracer:
the window's calls are the program's last ``len(ctx["call_s"])``
``fit_scanned`` spans (the reference imports nothing of the program).

For the LONGEST call of the window, its ``call=``, its stages and any
``xla_compile`` inside it go to stderr: where one call in some hundreds
stalls for seconds (PERF.md, Open questions), this says whether the
dispatch or the read was waiting, and whether a compile was the cause."""

import sys

import common

spans_of = common.module("layer_metrics", "program_spans")


def read(ctx):
    n = len(ctx.get("call_s") or ())
    if not n or spans_of.dropped():
        return None
    spans = spans_of.program_spans()
    calls = [s for s in spans if s["name"] == "fit_scanned"][-n:]
    if len(calls) < n:
        return None
    kids = spans_of.children_of(spans)

    def stage(call, name):
        return sum(s["seconds"] for s in kids.get(call["args"]["id"], [])
                   if s["name"] == name)

    dispatch = [stage(c, "fit_scanned_dispatch") for c in calls]
    worst = max(calls, key=lambda c: c["seconds"])
    compiles = [s for s in spans_of.descendants(worst, kids)
                if s["name"] == "xla_compile"]
    print(f"longest train call: call={worst['args'].get('call')} "
          f"{spans_of.ms(worst['seconds'])} ms = dispatch "
          f"{spans_of.ms(stage(worst, 'fit_scanned_dispatch'))} + read "
          f"{spans_of.ms(stage(worst, 'fit_scanned_read'))}; "
          f"{len(compiles)} xla_compile inside it"
          + "".join(f" ({spans_of.ms(s['seconds'])} ms)" for s in compiles)
          + f"; median call {spans_of.ms(common.median([c['seconds'] for c in calls]))}"
          f" ms over {n} calls", file=sys.stderr)
    return 1e3 * common.median(dispatch)
