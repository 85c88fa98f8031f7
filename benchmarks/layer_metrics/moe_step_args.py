"""What the two ``experts`` readers share: the window's ``serving_decode``
spans that carry the expert layer's arguments (a program without an expert
layer, or from before the spans had them, has none: nothing is read)."""


def decode_args(ctx, key):
    return [s["args"][key] for s in ctx.get("spans") or []
            if s["name"] == "serving_decode" and key in s["args"]]
