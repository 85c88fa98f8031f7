"""Median over the window's calls of host-clock seconds per call / steps per
call, in ms. The steadier statistic beside train_samples_per_s."""

import common


def read(ctx):
    if not ctx.get("call_s"):
        return None
    return 1e3 * common.median(ctx["call_s"]) / ctx["steps_per_call"]
