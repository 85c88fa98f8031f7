"""95th percentile, in ms, of the wait in the scheduler's queue (span
``serving_queue_wait``: submit -> the start of the admission that took the
request) over the requests submitted inside the window: the population of
``serve_ttft_p95_ms``. A request's span is an async pair, which the loop's
``ctx["spans"]`` (thread spans) leaves out, so it is read from the program's
tracer. Median and count go to stderr."""

import sys

import common

spans_of = common.module("layer_metrics", "program_spans")


def read(ctx):
    if "window" not in ctx or spans_of.dropped():
        return None
    t0, t1 = ctx["window"]
    waits = [1e3 * s["seconds"] for s in spans_of.program_spans(("b",))
             if s["name"] == "serving_queue_wait" and t0 <= s["start"] <= t1]
    if not waits:
        return None
    print(f"queue wait ms: p50={common.median(waits):.1f} "
          f"p95={common.percentile(waits, 95):.1f} over {len(waits)} "
          f"admissions", file=sys.stderr)
    return common.percentile(waits, 95)
