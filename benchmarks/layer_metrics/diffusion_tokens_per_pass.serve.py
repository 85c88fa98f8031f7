"""Tokens a forward pass yields a slot: the sum of ``dd_tokens`` (the
tokens a block step's blocks yield, before a commit cuts any) over the sum
of ``dd_passes`` (the passes the step ran, its store pass included) times
``slots``, over the window's ``serving_decode`` spans. At the static
schedule of one token a pass it reads just under ``B / (B + 1)``: a first
block's fixed prompt tail yields fewer tokens in as many passes. A program
that folds the store pass into the next block's first pass shows here.
Spans without the arguments (a model that yields one token a step):
nothing read."""


def read(ctx):
    steps = [s["args"] for s in ctx.get("spans") or []
             if s["name"] == "serving_decode" and "dd_passes" in s["args"]]
    runs = sum(a["dd_passes"] * a.get("slots", 0) for a in steps)
    if not runs:
        return None
    return sum(a["dd_tokens"] for a in steps) / runs
