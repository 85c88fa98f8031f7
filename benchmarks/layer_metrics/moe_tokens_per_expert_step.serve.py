"""Mean over the window's ``serving_decode`` spans of ``moe_held`` (the
step's picks that went to held experts) over held experts x expert layers:
the tokens a held expert sees a decode step. How near the experts' load is
to the deployment's, where a rank's slots give each expert
``slots * moe_topk / router outputs`` of them."""

import common

steps = common.module("layer_metrics", "moe_step_args")


def read(ctx):
    held = steps.decode_args(ctx, "moe_held")
    cfg = ctx["cell"]["cfg"]
    if not held:
        return None
    return sum(held) / len(held) / (cfg["n_routed_experts"] * cfg["num_layers"])
