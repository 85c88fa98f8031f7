"""Least time by the roofline (bytes-bound: ``counts/<family>.py``
``state_bytes_per_step``, every active slot's UNPADDED state read once and
written once a decode step, plus the step's q, k, v and g) of the decode
steps the traced window ran, over the summed device time of the retention
decode kernel's events there. The events are found by the NAME the program
gives the kernel (``pallas_call(name="retention_decode")``, which the trace
prints as ``%retention_decode.N = ... custom_call_target=
"tpu_custom_call"``), never by an operand's shape; a decode step is one such
event a layer, and its active slots are the mean ``slots=`` of the window's
``serving_decode`` spans. Nothing found (the op on its generic path, a
program without it): nothing returned, never 0."""

import common
import trace_reduce

PATTERN = ("%retention_decode", "custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    found = trace_reduce.matching(tr, PATTERN)
    if not found or found[0] <= 0:
        return None
    cfg = ctx["cell"]["cfg"]
    counts = common.module("counts", cfg["family"])
    if not hasattr(counts, "state_bytes_per_step"):
        return None
    slots = [s["args"]["slots"] for s in ctx.get("spans") or []
             if s["name"] == "serving_decode" and "slots" in s["args"]]
    if not slots:
        return None
    steps = found[1] / cfg["num_hidden_layers"]
    need = steps * counts.state_bytes_per_step(cfg, sum(slots) / len(slots))
    least = need / common.peaks_of(ctx["kind"])["bytes_per_s"]
    return 100.0 * least / found[0]
