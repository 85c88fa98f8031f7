"""The share of the serving worker's steps that the collector took, in
percent: 100 x the summed ``gc_ms`` over the summed wall time of the
window's ``serving_step`` spans (``gc_ms``: every pass on the worker's
thread while the step was open, generation 0 included).

On stderr: the ``host_gc`` spans of the window (passes of generation 1 and
2, on any thread) by generation and thread, the longest of them, and the
same over the traced seconds alone beside the run's device idle share; and
the process's passes by generation so far
(``dl4j_tpu_gc_pause_seconds{generation}``), generation 0 included."""

import sys

import common

host = common.module("layer_metrics", "host_accounting")


def passes(spans):
    by: dict = {}
    for s in spans:
        key = (s["args"].get("generation"), s["args"].get("thread"))
        n, secs = by.get(key, (0, 0.0))
        by[key] = (n + 1, secs + s["seconds"])
    return "; ".join(f"generation {g} on {t}: {n} ({1e3 * secs:.3f} ms)"
                     for (g, t), (n, secs) in sorted(by.items(), key=str))


def pct(steps):
    return (100.0 * sum(s["args"]["gc_ms"] for s in steps)
            / (1e3 * sum(s["seconds"] for s in steps)))


def process_passes():
    """The program's histogram of every pass, by generation; empty where
    the program has none."""
    from deeplearning4j_tpu import observe

    return {dict(inst.labels).get("generation"): (inst.count, inst.max)
            for inst in observe.metrics().instruments()
            if inst.name == "dl4j_tpu_gc_pause_seconds"}


def read(ctx):
    spans = ctx.get("spans") or []
    steps = host.accounted(spans, "serving_step")
    if steps is None:
        return None
    gcs = [s for s in spans if s["name"] == "host_gc"]
    longest = max(gcs, key=lambda s: s["seconds"]) if gcs else None
    t0 = (ctx.get("window") or (min(s["start"] for s in steps),))[0]
    print(f"collector in the serving steps: {pct(steps):.4f}% of "
          f"{len(steps)} steps; host_gc in the window: "
          + (passes(gcs) or "none")
          + (f"; longest {host.described(longest)} at "
             f"{longest['start'] - t0:.3f} s" if longest else ""),
          file=sys.stderr)
    traced = host.inside(steps, ctx.get("traced"))
    if traced:
        print(f"  traced seconds alone: {pct(traced):.4f}% of {len(traced)} "
              f"steps; host_gc "
              + (passes(host.inside(gcs, ctx.get("traced"))) or "none")
              + f"; {host.idle_note(ctx, 'serve')}", file=sys.stderr)
    seen = process_passes()
    if seen:
        print("  the process's passes so far (count, longest ms): " + "; ".join(
            f"generation {g} {n}, {1e3 * (mx or 0):.3f}"
            for g, (n, mx) in sorted(seen.items())), file=sys.stderr)
    return pct(steps)
