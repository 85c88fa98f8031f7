"""Continuous-batching generative serving (docs/SERVING.md).

The generative-inference tier ROADMAP item 2 names: a decoder-only
transformer (``models/gpt.py``) served through

* :class:`PagedKVCache` — block-paged KV memory with a free-list allocator
  (the PagedAttention/vLLM layout), sized once at server start;
* :class:`SlotScheduler` — iteration-level (Orca-style) continuous
  batching: admit into free slots / evict finished + overflowing sequences
  between decode steps;
* :class:`GenerativeEngine` — the compiled prefill/decode/write functions
  whose jit signatures depend only on server configuration (compile once,
  serve any mix of sequences) plus temperature/top-k/top-p sampling with
  per-slot split PRNG keys (``serving/sampling.py``); SUPERVISED since the
  robustness tier (docs/ROBUSTNESS.md): worker crashes restart under
  capped backoff with retry re-admission, per-request deadlines, and a
  bounded-queue admission gate that sheds overload as a terminal ``shed``
  reason — exercised by ``make chaos-smoke`` over the
  ``deeplearning4j_tpu/faults/`` injection points.

* :class:`RadixPrefixCache` (``serving/prefix.py``) — shared-prompt KV
  reuse: a radix tree over token sequences whose nodes hold refcounted
  cache pages, so repeated system prompts/few-shot prefixes map by
  reference and only the uncached suffix prefills
  (copy-on-write for mid-page divergence, LRU leaf eviction under a page
  budget, per-class pre-warm + pinning via the frontend's
  ``ClassPolicy.shared_prefix``; what it buys on the chip is not
  measured: no cell runs it, ``ROADMAP.md`` W1);

* :class:`SpeculativeDecoder` (``serving/speculative.py``) — speculative
  decoding (draft-then-verify, lossless): ``GenerativeEngine(spec_k=K,
  draft_model=...)`` runs a small draft model over a dense per-slot KV
  cache to propose K greedy tokens per step, verifies all of them in ONE
  target forward (``models.gpt.gpt_verify``, the fifth compiled fn), and
  commits the agreed prefix plus the target's correction token —
  bit-identical outputs at 1..K+1 tokens per target step, rollback as an
  O(1) length rewind (not measured on the chip: no cell speculates,
  ``ROADMAP.md`` W4);

* :class:`SLOFrontend` (``serving/frontend.py``) — the SLO-driven
  admission layer: priority classes over a priority-ordered pending
  queue, token-bucket rate limits, predictive early shed against
  per-request deadlines, an ``ok``/``degraded``/``shedding`` hysteresis
  ladder, and a circuit breaker on the supervisor's restart rate —
  overload becomes goodput management instead of a failure mode
  (``serving/overload.py`` is the ramp ``tools/chaos.py`` drives it with).

* :class:`ClusterRouter` (``serving/cluster.py``) — N engines behind one
  health- and prefix-affinity-routed front: whole-engine death (restart
  budget spent, or the hard ``engine_death`` fault) becomes a managed
  failure domain — in-flight retryable requests migrate to survivors at
  queue front with their original submit time and priority, pinned
  prefixes re-warm on the destination, and the frontend's per-engine
  circuit breaker quarantines only the dead engine (``make
  cluster-chaos-smoke`` gates it).

Serve it directly or through the ``ParallelInference.generative`` facade
(``parallel/mesh.py``). The serving cells of ``benchmarks/run.py``
(``BENCHMARK.json``) measure tokens/s and the first-token tail on the chip;
``PERF.md`` says what was found.
"""

from deeplearning4j_tpu.serving.cache import PagedKVCache
from deeplearning4j_tpu.serving.cluster import ClusterRouter
from deeplearning4j_tpu.serving.engine import GenerativeEngine
from deeplearning4j_tpu.serving.prefix import PrefixMatch, RadixPrefixCache
from deeplearning4j_tpu.serving.frontend import (
    ClassPolicy,
    LadderThresholds,
    OVERLOAD_STATES,
    SLOFrontend,
    default_classes,
)
from deeplearning4j_tpu.serving.sampling import sample_tokens
from deeplearning4j_tpu.serving.scheduler import (
    FINISH_REASONS,
    GenerationRequest,
    GenerationResult,
    SlotScheduler,
)
from deeplearning4j_tpu.serving.speculative import (
    SpeculativeDecoder,
    perturbed_draft,
)

__all__ = [
    "PagedKVCache", "GenerativeEngine", "ClusterRouter", "sample_tokens",
    "GenerationRequest", "GenerationResult", "SlotScheduler",
    "FINISH_REASONS", "SLOFrontend", "ClassPolicy", "LadderThresholds",
    "OVERLOAD_STATES", "default_classes", "RadixPrefixCache",
    "PrefixMatch", "SpeculativeDecoder", "perturbed_draft",
]
