"""95th percentile of ALL gaps between consecutive tokens that ended inside
the window, from the program's ``GenerationResult.intertoken_s``. The closed
loop runs the bank full, so this tail sits on a ladder (a decode step plus
0, 1, 2 ... prefills) and swings between two rungs from run to run: it is
read here, beside the bounded metrics, not among them (PERF.md)."""


def read(ctx):
    return (ctx.get("stats") or {}).get("itl_p95_ms")
