"""Median over the window's ``serving_step`` spans of: the step's duration
less the ``*_read`` spans among its descendants (``serving_decode_read``,
``serving_prefill_read``: the host blocked on the device's answer), in ms.
What the host spends on a step while the device has nothing it is waiting
on. The median and 95th percentile of each stage, and the traced run's own
tokens per second, go to stderr."""

import sys

import common

spans_of = common.module("layer_metrics", "program_spans")


def read(ctx):
    spans = ctx.get("spans") or []
    steps = [s for s in spans if s["name"] == "serving_step"]
    if not steps or spans_of.dropped():
        return None
    kids = spans_of.children_of(spans)
    host, stages = [], {}
    for step in steps:
        below = spans_of.descendants(step, kids)
        reads = sum(s["seconds"] for s in below
                    if s["name"].endswith("_read"))
        host.append(1e3 * (step["seconds"] - reads))
        for s in below:
            stages.setdefault(s["name"], []).append(1e3 * s["seconds"])
    steps_ms = [1e3 * s["seconds"] for s in steps]
    stages["serving_step"] = steps_ms
    print("serving step ms, median/p95 of each stage (spans a step): "
          + "; ".join(f"{name} {common.median(v):.3f}/"
                      f"{common.percentile(v, 95):.3f} "
                      f"(x{len(v) / len(steps):.2f})"
                      for name, v in sorted(stages.items()))
          + f"; host {common.median(host):.3f}/"
          f"{common.percentile(host, 95):.3f}; {len(steps)} steps", file=sys.stderr)
    direct = sum(c["seconds"] for step in steps
                 for c in kids.get(step["args"]["id"], []))
    print(f"serving steps: their direct children cover "
          f"{1e3 * direct / sum(steps_ms):.4f} of their summed duration",
          file=sys.stderr)
    if ctx.get("stats") and ctx.get("window"):
        t0, t1 = ctx["window"]
        print(f"serving window: {ctx['stats']['tokens']} tokens in "
              f"{t1 - t0:.3f} s = {ctx['stats']['tokens'] / (t1 - t0):.2f} "
              f"tokens/s; steps cover {sum(steps_ms) / 1e3 / (t1 - t0):.4f} "
              f"of it", file=sys.stderr)
    return common.median(host)
