"""``latent_attn_roofline.serve``'s reading with the bytes taken from the
counts of the configuration's own family (``counts/<family>.py``
``latent_bytes_per_token``): least time by the roofline (bytes-bound) of the
decode tokens that arrived inside the traced window, over the summed device
time of the latent decode attention kernel's events there. The events are
found by the NAME the program gives the kernel (that reader's ``PATTERN``),
never by an operand's shape. Nothing found (the op on its generic path, a
program without it): nothing returned, never 0."""

import common
import trace_reduce

PATTERN = common.module("layer_metrics", "latent_attn_roofline.serve").PATTERN


def read(ctx):
    tr, span = ctx.get("trace"), ctx.get("traced")
    if not tr or not span:
        return None
    found = trace_reduce.matching(tr, PATTERN)
    if not found or found[0] <= 0:
        return None
    cfg = ctx["cell"]["cfg"]
    counts = common.module("counts", cfg["family"])
    t0, t1 = span
    need = 0.0
    for p, times in ctx["tokens"]:
        for i, t in enumerate(times[1:], start=1):
            if t0 <= t <= t1:
                need += counts.latent_bytes_per_token(cfg, p + i)
    if not need:
        return None
    least = need / common.peaks_of(ctx["kind"])["bytes_per_s"]
    return 100.0 * least / found[0]
