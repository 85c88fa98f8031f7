"""The wall time of the longest ``serving_step`` of the window, in ms: where
one iteration in thousands stalls (a stall: over ten times the median), this
is its length, and stderr names what the worker was doing in it.

On stderr, for that step: its direct children (each stage with the host's
accounting where it keeps one, the reads among them), the step's own
``cpu_ms`` and ``gc_ms``, the ``host_gc`` (with the other collector
callbacks' time around the pass) and ``xla_compile`` spans that share time
with it on any thread, and whether it lies inside the traced seconds; then
the five longest steps with their offsets into the window, and the longest
step of the traced seconds alone, with its stages where it is another,
beside the run's device idle share."""

import sys

import common

host = common.module("layer_metrics", "host_accounting")
spans_of = host.spans_of


def inside_it(step, spans, kids):
    """A step's stages, its reads and the collector's passes and compiles
    beside it, on stderr."""
    stages = sorted(kids.get(step["args"]["id"], []),
                    key=lambda s: s["start"])
    reads = host.reads_below(step, kids)
    others = host.overlapping(spans, step, ("host_gc", "xla_compile"))
    print("  its stages: " + "; ".join(host.described(s) for s in stages)
          + "; its reads: " + ("; ".join(host.described(s) for s in reads)
                               or "none"), file=sys.stderr)
    print("  beside it, on any thread: "
          + ("; ".join(host.described(s) for s in others) or "no host_gc, "
             "no xla_compile"), file=sys.stderr)


def read(ctx):
    spans = ctx.get("spans") or []
    steps = host.accounted(spans, "serving_step")
    if steps is None:
        return None
    kids = spans_of.children_of(spans)
    worst = max(steps, key=lambda s: s["seconds"])
    median = common.median([s["seconds"] for s in steps])
    t0 = (ctx.get("window") or (min(s["start"] for s in steps),))[0]
    traced = host.inside(steps, ctx.get("traced"))
    print(f"longest serving step: step={worst['args'].get('step')} "
          f"{spans_of.ms(worst['seconds'])} ms at "
          f"{worst['start'] - t0:.3f} s into the window, "
          f"{worst['seconds'] / median:.1f}x the median "
          f"{spans_of.ms(median)} ms; {host.usage(worst)}; "
          f"{'inside' if worst in traced else 'outside'} the traced seconds",
          file=sys.stderr)
    inside_it(worst, spans, kids)
    longest = sorted(steps, key=lambda s: -s["seconds"])[:5]
    print("  five longest steps (ms @ s into the window): " + ", ".join(
        f"{spans_of.ms(s['seconds'])} @ {s['start'] - t0:.3f}"
        for s in longest) + f"; {len(steps)} steps, "
          f"{sum(s['seconds'] > 10 * median for s in steps)} over ten times "
          f"the median", file=sys.stderr)
    if traced:
        top = max(traced, key=lambda s: s["seconds"])
        print(f"  traced seconds alone: longest step "
              f"{spans_of.ms(top['seconds'])} ms of {len(traced)} at "
              f"{top['start'] - t0:.3f} s; {host.usage(top)}; "
              f"{host.idle_note(ctx, 'serve')}", file=sys.stderr)
        if top is not worst:
            inside_it(top, spans, kids)
    return 1e3 * worst["seconds"]
