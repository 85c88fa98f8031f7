"""Least time by the roofline (counts/flash_attention.py: operations-bound
at BERT-base's shapes, forward and backward) for the samples of the traced
window, over the summed device time of the flash attention kernels' events
there. The kernels have no names of their own yet: their events are the
Mosaic custom calls with an operand of the folded shape
``[batch*heads,seq,head size]``, which no other kernel of the step has."""

import common
import trace_reduce


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    cfg, mix = ctx["cell"]["cfg"], ctx["cell"]["mix"]
    heads = cfg["num_attention_heads"]
    shape = (f"[{mix['batch'] * heads},{mix['seq']},"
             f"{cfg['hidden_size'] // heads}]")
    found = trace_reduce.matching(
        tr, ('custom_call_target="tpu_custom_call"', shape))
    if not found or found[0] <= 0:
        return None
    counts = common.module("counts", "flash_attention")
    samples = ctx["rate"] * tr["window_s"]
    least, _ = counts.least_seconds(cfg, mix["seq"], samples,
                                    common.peaks_of(ctx["kind"]))
    return 100.0 * least / found[0]
