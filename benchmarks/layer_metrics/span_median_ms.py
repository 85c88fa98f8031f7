"""Reader ``span_median_ms``: median duration, in ms, of the program's spans
of one name inside the window."""

import common


def read(ctx, *, span):
    durs = [s["seconds"] for s in ctx.get("spans", []) if s["name"] == span]
    return 1e3 * common.median(durs) if durs else None
