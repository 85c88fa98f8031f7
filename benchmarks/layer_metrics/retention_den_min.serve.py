"""The smallest ``ret_den_min`` over the window's ``serving_decode`` spans:
the smallest normaliser ``phi(q)^T z`` any query head of any layer and slot
divided by in any decode step. Near the normaliser's epsilon (1e-6) the
division is noise; a program that keeps its state in fewer bits, or decays
it faster to go faster, shows here, beside ``served_logit_gap``. A program
without a retention layer has no such argument: nothing is read."""

import common

steps = common.module("layer_metrics", "moe_step_args")


def read(ctx):
    dens = steps.decode_args(ctx, "ret_den_min")
    return min(dens) if dens else None
