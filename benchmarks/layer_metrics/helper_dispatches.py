"""Reader ``helper_dispatches``: how often the registry took a Pallas TPU
helper (observe.dispatch_summary(), impl=tpu), set-up included: a program is
traced once, so this counts call sites taken, not executions."""


def read(ctx):
    counts = ctx.get("dispatch") or {}
    taken = sum(n for key, n in counts.items() if key.split("/")[1] == "tpu")
    return float(taken) if taken else None
