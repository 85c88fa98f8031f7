"""Least time by the roofline (bytes-bound: ``counts/sdar.py``
``block_bytes_per_pass``, the UNPADDED key and value rows every pass of a
block needs, the context before it and its own rows, over the blocks whose
tokens arrived inside the traced window, ``block_length + 1`` passes each)
over the summed device time of the block decode attention kernel's events
there. The events are found by the NAME the program gives the kernel
(``pallas_call(name="block_decode_attention")``, which the trace prints as
``%block_decode_attention.N = ... custom_call_target="tpu_custom_call"``),
never by an operand's shape. Nothing found (the op on its generic path, a
program without it): nothing returned, never 0."""

import common
import trace_reduce

PATTERN = ("%block_decode_attention", "custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    tr, span = ctx.get("trace"), ctx.get("traced")
    if not tr or not span:
        return None
    found = trace_reduce.matching(tr, PATTERN)
    if not found or found[0] <= 0:
        return None
    cfg = ctx["cell"]["cfg"]
    counts = common.module("counts", cfg["family"])
    passes = int(cfg["block_length"]) + 1
    t0, t1 = span
    need = 0.0
    for p, times in ctx["tokens"]:
        for start, t in counts.blocks_of(cfg, p, times):
            if t0 <= t <= t1:
                need += passes * counts.block_bytes_per_pass(cfg, start)
    if not need:
        return None
    least = need / common.peaks_of(ctx["kind"])["bytes_per_s"]
    return 100.0 * least / found[0]
