"""Reader ``mfu``: the whole step's share of the chip's peak. The model's
operations per second of the traced run, counted from shapes by
``counts/<family>.py`` (the function the metric's file names), over chips x
peak FLOP/s of the device_kind."""

import common


def read(ctx, *, flops_per_s):
    cell = ctx["cell"]
    counts = common.module("counts", cell["cfg"]["family"])
    done = getattr(counts, flops_per_s)(cell["cfg"], cell["mix"], ctx)
    if not done:
        return None
    peak = common.peaks_of(ctx["kind"])["flops_per_s"] * ctx["chips"]
    return 100.0 * done / peak
