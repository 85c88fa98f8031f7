"""Mean over the window's ``serving_decode`` spans of ``moe_max_over_mean``:
the fullest held expert's tokens over the mean of a held expert's, over the
step's expert layers. 1 is perfectly even; the grouped product's time follows
the fullest group."""

import common

steps = common.module("layer_metrics", "moe_step_args")


def read(ctx):
    ratios = steps.decode_args(ctx, "moe_max_over_mean")
    return sum(ratios) / len(ratios) if ratios else None
