"""Reader ``programs_compiled``: how many programs XLA built (compiled, or
loaded from the persistent cache: the same number either way) before the
window's end, set-up included. The program counts each where it is built
(``dl4j_tpu_xla_programs_total``) and gives it an ``xla_compile`` span at the
same moment; the reference's own compiles come after the window, so the
spans that start after its end are taken off the counter. The window's end
is ``ctx["window"][1]`` where the loop gives one, else the end of the last
``fit_scanned`` span. A count, never a speed: it repeats exactly from run to
run. How many fell INSIDE the window (there should be none: every shape is
warmed in set-up) goes to stderr with the span each was built under."""

import sys

import common

spans_of = common.module("layer_metrics", "program_spans")


def read(ctx):
    from deeplearning4j_tpu import observe

    if not hasattr(observe, "install_xla_listener") or spans_of.dropped():
        return None
    spans = spans_of.program_spans()
    if ctx.get("window"):
        t0, t1 = ctx["window"]
    else:
        calls = [s for s in spans if s["name"] == "fit_scanned"]
        calls = calls[-len(ctx.get("call_s") or ()):]
        if not calls:
            return None
        t0, t1 = calls[0]["start"], calls[-1]["start"] + calls[-1]["seconds"]
    built = [s for s in spans if s["name"] == "xla_compile"
             and "cached" in s["args"]]
    total = int(observe.metrics().counter("dl4j_tpu_xla_programs_total").value)
    before = total - sum(s["start"] > t1 for s in built)
    inside = [s for s in built if t0 <= s["start"] <= t1]
    names = {s["args"].get("id"): s["name"] for s in spans}
    print(f"programs compiled: {before} before the window's end, "
          f"{len(inside)} inside the window"
          + "".join(f" [{spans_of.ms(s['seconds'])} ms under "
                    f"{names.get(s['args'].get('parent'), 'no span')}]"
                    for s in inside)
          + f"; {sum(bool(s['args']['cached']) for s in built)} of "
          f"{len(built)} spans still held came from the persistent cache",
          file=sys.stderr)
    return float(before) if before > 0 else None
