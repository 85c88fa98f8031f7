"""Expert-layer statistics (docs/OBSERVABILITY.md § Expert layer): where a
step's (token, expert) picks went and how evenly the held experts were
loaded. The numbers are made on the device by
``parallel.moe.moe_topk_share`` and reach the host in the read the serving
step already makes; this turns them into counters, one histogram and the
arguments of the step's span."""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.observe.registry import default_registry

# max over mean of the tokens a held expert: 1 is perfectly even
_LOAD_BOUNDS = (1.0, 1.25, 1.5, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0,
                32.0, 64.0)


def note_moe(stats, span=None, *, first_expert: int = 0,
             decode_step: bool = False, grouped=None) -> dict:
    """``stats``: (expert layers, held experts + 2) integers — tokens a held
    expert, then the picks to zero experts and to absent experts, a layer.
    Counts them (``dl4j_tpu_moe_picks_total{kind}``,
    ``dl4j_tpu_moe_expert_tokens_total{layer, expert}``), observes the load
    ratio of a decode step (``dl4j_tpu_moe_load_max_over_mean``) and sets
    ``moe_held``/``moe_zero``/``moe_absent``/``moe_max_over_mean`` on
    ``span``. ``grouped``: ``(path, tile)`` as ``parallel.moe.grouped_path``
    gives them, how the program's grouped products engaged: counted
    (``dl4j_tpu_moe_grouped_steps_total{path, tile}``) and set on ``span`` as
    ``moe_path``/``moe_tile``. Returns what it set."""
    stats = np.asarray(stats)
    per_expert = stats[:, :-2]
    held, zero, absent = (int(per_expert.sum()), int(stats[:, -2].sum()),
                          int(stats[:, -1].sum()))
    m = default_registry()
    for kind, n in (("held", held), ("zero", zero), ("absent", absent)):
        m.counter("dl4j_tpu_moe_picks_total", kind=kind).inc(n)
    for layer, row in enumerate(per_expert):
        for e, n in enumerate(row):
            if n:
                m.counter("dl4j_tpu_moe_expert_tokens_total", layer=str(layer),
                          expert=str(first_expert + e)).inc(int(n))
    # the step's load ratio: the fullest held expert over the mean, over
    # the step's expert layers together
    ratio = (float(per_expert.max()) * per_expert.size / held) if held else 0.0
    if decode_step and held:
        m.histogram("dl4j_tpu_moe_load_max_over_mean",
                    bounds=_LOAD_BOUNDS).observe(ratio)
    out = {"moe_held": held, "moe_zero": zero, "moe_absent": absent,
           "moe_max_over_mean": round(ratio, 4)}
    if grouped is not None:
        path, tile = grouped
        m.counter("dl4j_tpu_moe_grouped_steps_total", path=path,
                  tile=str(tile)).inc()
        out.update(moe_path=path, moe_tile=int(tile))
    if span is not None:
        span.set(**out)
    return out
