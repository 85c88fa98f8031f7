"""The largest ``hc_residual`` over the window's ``serving_decode`` spans:
how far the worst stream-mixing matrix ``H_res`` of any decode step was from
doubly stochastic after its Sinkhorn iterations (the largest ``|row sum -
1|`` or ``|column sum - 1|``). A program that shortens the iteration to go
faster shows here, beside ``served_logit_gap``. A program without a
hyper-connected residual has no such argument: nothing is read."""

import common

steps = common.module("layer_metrics", "moe_step_args")


def read(ctx):
    residuals = steps.decode_args(ctx, "hc_residual")
    return max(residuals) if residuals else None
