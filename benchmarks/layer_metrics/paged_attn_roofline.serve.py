"""Least time by the roofline (bytes-bound: counts/paged_attention.py, over
the decode tokens that arrived inside the traced window) over the summed
device time of the paged decode attention kernel's events there. The kernel
has no name of its own yet: its events are the Mosaic custom calls of the
decode program, which the trace prints as ``%decode.N = ...
custom_call_target="tpu_custom_call"``."""

import common
import trace_reduce

PATTERN = ("custom_call_target=\"tpu_custom_call\"", "%decode")


def read(ctx):
    tr, span = ctx.get("trace"), ctx.get("traced")
    if not tr or not span:
        return None
    found = trace_reduce.matching(tr, PATTERN)
    if not found or found[0] <= 0:
        return None
    counts = common.module("counts", "paged_attention")
    cfg = ctx["cell"]["cfg"]
    t0, t1 = span
    need = 0.0
    for p, times in ctx["tokens"]:
        for i, t in enumerate(times[1:], start=1):
            if t0 <= t <= t1:
                need += counts.bytes_per_token(cfg, p + i)
    if not need:
        return None
    least = need / common.peaks_of(ctx["kind"])["bytes_per_s"]
    return 100.0 * least / found[0]
