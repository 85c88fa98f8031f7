"""Randomized-shape serving replay: a correctness substrate, not a bench.

:func:`run_randomized_replay` drives a fresh inline
:class:`GenerativeEngine` (no worker thread, one request at a time) with
prompt and generation lengths drawn across the whole admissible range,
some requests sharing a system prompt, the prefix cache and speculative
decoding both armed, and reports what the RecompileLedger saw: the
``first_compile`` and ``cache_hit`` keys, the ``new_shape`` count, the
greedy outputs. It times nothing. Its callers make the assertions:
``tools/shapetrace.py`` and ``tests/test_graftshape.py`` (every event
attributes to the static jit-site inventory, zero ``new_shape``),
``tools/aot.py`` and ``tests/test_export.py`` (a warm restart restores
every program as a ``cache_hit`` with bit-identical outputs).

What the prefix cache and speculation do to a cell's tokens/s or first
token is the benchmark's to say (``benchmarks/run.py``, ``PERF.md``); no
cell runs either yet (``ROADMAP.md`` W1, W4).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# one definition of "a serving recompile" for every gate harness
from deeplearning4j_tpu.serving.overload import _serving_new_shape_count


def _serving_first_compile_keys(before: int) -> List[str]:
    """The serving-graph ``first_compile`` ledger keys recorded after
    event index ``before`` — the gate's "exactly the expected compiled
    functions" evidence."""
    from deeplearning4j_tpu import observe

    evs = observe.ledger().events()
    return sorted(e.key for e in evs[before:]
                  if e.graph == "serving" and e.cause == "first_compile")


def _serving_cache_hit_keys(before: int) -> List[str]:
    """The serving-graph ``cache_hit`` ledger keys after event index
    ``before`` — the AOT warm-boot gate's "restored, not recompiled"
    evidence (deduplicated: polymorphic fns record one hit per
    signature)."""
    from deeplearning4j_tpu import observe

    evs = observe.ledger().events()
    return sorted({e.key for e in evs[before:]
                   if e.graph == "serving" and e.cause == "cache_hit"})


def run_randomized_replay(*, n_requests: int = 16, seed: int = 0,
                          vocab: int = 256, max_prompt: int = 32,
                          page_size: int = 8, suffix_bucket: int = 8,
                          gen_max: int = 6, spec_k: int = 3,
                          max_slots: int = 2, n_prefixes: int = 2,
                          draft_noise: float = 1e-2,
                          model=None) -> Dict[str, Any]:
    """Shape-DIVERSE replay — the graftshape cross-validation workload
    (the serving leg of ``tools/shapetrace.py`` / the ``shapetrace`` gate
    stage, and every leg of ``tools/aot.py``).

    Prompt lengths are drawn from the FULL ``1..max_prompt`` range
    (deliberately straddling page and ``suffix_bucket`` boundaries),
    generation lengths vary per request,
    and a fraction of requests share one of ``n_prefixes`` system
    prompts so both the full-prefill and suffix-prefill paths fire —
    with the prefix cache AND speculative decoding armed at once.  The
    engine's bucketing contract says none of that diversity may reach a
    jit signature: the ledger must show only ``first_compile`` events,
    ZERO serving ``new_shape``.  That is the assertion this function
    exists to feed (the caller makes it — this function only reports).
    """
    from deeplearning4j_tpu import observe
    from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
    from deeplearning4j_tpu.serving import GenerativeEngine
    from deeplearning4j_tpu.serving.speculative import perturbed_draft

    if model is None:
        cfg = GptConfig.tiny(vocab_size=vocab,
                             max_position=4 * max_prompt)
        model = GptModel(cfg, seed=0)
    cfg = model.cfg
    draft_model = perturbed_draft(model, scale=draft_noise, seed=1)
    pages_per_seq = -(-(max_prompt + gen_max + spec_k + 1)
                      // page_size) + 1
    prefix_pages = n_prefixes * (-(-max_prompt // page_size))
    # construction includes the AOT warm boot when $DL4J_TPU_COMPILE_CACHE
    # is set (serving/aot.py)
    eng = GenerativeEngine(
        model, max_slots=max_slots, page_size=page_size,
        num_pages=max_slots * pages_per_seq + prefix_pages,
        max_pages_per_seq=pages_per_seq, max_prompt=max_prompt, seed=0,
        prefix_pages=prefix_pages, suffix_bucket=suffix_bucket,
        spec_k=spec_k, draft_model=draft_model)
    led_before = len(observe.ledger().events())
    new_shape_before = _serving_new_shape_count()

    r = np.random.RandomState(seed)
    # shared system prompts sized to cross a page boundary, so prefix
    # hits exercise the suffix-prefill path too
    pfx_len = max(page_size + 1, max_prompt // 2)
    prefixes = [r.randint(1, cfg.vocab_size, size=pfx_len).astype(np.int32)
                for _ in range(n_prefixes)]
    plan = []
    for i in range(n_requests):
        if i % 3 == 0 and n_prefixes:
            # shared-prefix request with a ragged unique tail
            pfx = prefixes[int(r.randint(n_prefixes))]
            tail_max = max(1, max_prompt - pfx_len)
            tail = r.randint(1, cfg.vocab_size,
                             size=int(r.randint(1, tail_max + 1))) \
                .astype(np.int32)
            plan.append(np.concatenate([pfx, tail]))
        else:
            # fully random length across the whole admissible range
            plen = int(r.randint(1, max_prompt + 1))
            plan.append(r.randint(1, cfg.vocab_size,
                                  size=plen).astype(np.int32))
    gens = [int(r.randint(1, gen_max + 1)) for _ in range(n_requests)]

    def run_one(prompt, n_gen):
        fut = eng.submit(prompt, max_new_tokens=n_gen, eos_token=-1)
        while eng.scheduler.has_work():
            eng.step()
        return fut.result(timeout=0)

    results = [run_one(p, g) for p, g in zip(plan, gens)]
    eng.check_invariants()

    reasons: Dict[str, int] = {}
    for res in results:
        reasons[res.finish_reason] = reasons.get(res.finish_reason, 0) + 1
    return {
        "requests": n_requests,
        "outputs": [res.tokens.tolist() for res in results],
        "prompt_lens": sorted({len(p) for p in plan}),
        "gen_lens": sorted(set(gens)),
        "reasons": dict(sorted(reasons.items())),
        "all_terminal": all(res.finish_reason in ("eos", "length")
                            for res in results),
        "generated_tokens": int(sum(len(res.tokens) for res in results)),
        "prefix_hit_tokens": int(sum(res.prefix_hit_tokens
                                     for res in results)),
        "new_shape_events": max(
            0, _serving_new_shape_count() - new_shape_before),
        "first_compile_keys": _serving_first_compile_keys(led_before),
        "cache_hit_keys": _serving_cache_hit_keys(led_before),
    }
