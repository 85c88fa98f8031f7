"""The share of the serving worker's own time that it spent on a core, in
percent: 100 x the sum over the window's ``serving_step`` spans of (the
step's ``cpu_ms`` less its ``*_read`` descendants') over the sum of (the
step's wall time less its reads). The reads are the host blocked on the
device's answer and are left out of both; what is left is the host's part
of an iteration, and where it is off the core it waited (the GIL, a lock)
or was preempted.

On stderr: the same over the traced seconds alone, beside the run's device
idle share."""

import sys

import common

host = common.module("layer_metrics", "host_accounting")
spans_of = host.spans_of


def share(steps, kids):
    """(percent on a core or None, host ms) over ``steps``."""
    cpu = wall = 0.0
    for step in steps:
        reads = [r for r in host.reads_below(step, kids)
                 if "cpu_ms" in r["args"]]
        cpu += step["args"]["cpu_ms"] - sum(r["args"]["cpu_ms"] for r in reads)
        wall += 1e3 * (step["seconds"] - sum(r["seconds"] for r in reads))
    return (100.0 * cpu / wall if wall > 0 else None), wall


def line(label, steps, kids):
    pct, wall = share(steps, kids)
    return (f"{label}: {len(steps)} steps, host part {wall / 1e3:.3f} s, "
            + (f"on a core {pct:.2f}%" if pct is not None else "no host part"))


def read(ctx):
    spans = ctx.get("spans") or []
    steps = host.accounted(spans, "serving_step")
    if steps is None:
        return None
    kids = spans_of.children_of(spans)
    print("serving worker on a core, " + line("window", steps, kids),
          file=sys.stderr)
    traced = host.inside(steps, ctx.get("traced"))
    if traced:
        print("  " + line("traced seconds alone", traced, kids)
              + f"; {host.idle_note(ctx, 'serve')}", file=sys.stderr)
    return share(steps, kids)[0]
