"""Mean ``slots=`` of the serving_decode spans inside the window over the
engine's max_slots, in percent."""


def read(ctx):
    used = [s["args"].get("slots") for s in ctx.get("spans", [])
            if s["name"] == "serving_decode" and "slots" in s["args"]]
    if not used or not ctx.get("max_slots"):
        return None
    return 100.0 * sum(used) / len(used) / ctx["max_slots"]
