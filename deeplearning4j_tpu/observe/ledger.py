"""Recompile ledger — every jit cache miss, with its cause, on the record.

Item 1 of the ROADMAP (shape-polymorphic AOT serving) exists because diverse
traffic can trigger a recompile storm; this ledger makes the storm VISIBLE
before that item fixes it. ``SameDiff`` (autodiff/samediff.py) and the
network classes (nn/multilayer.py, nn/graph.py) report every compilation —
a ``_jit_cache`` miss or a new input shape/dtype signature hitting a cached
jit wrapper — as one :class:`CompileEvent` carrying:

* ``graph``/``key``: which model and which cached function (exec / grad /
  train_step / output ...),
* ``signature``: the input shape/dtype signature that compiled,
* ``cause``: ``first_compile`` | ``new_shape`` | ``graph_mutation`` |
  ``constant_rebind`` | ``variable_rebind`` | ``cache_hit`` — the
  invalidation that forced the miss (SameDiff threads the cause from the
  exact `_jit_cache.clear()` sites); ``cache_hit`` marks a fn restored
  from the persistent AOT export cache (autodiff/export.py) — a warm
  restore is a compile *event* (visible, attributable) but not a fresh
  XLA compile,
* ``stats``: the live ``OptimizeStats`` when the optimizer produced one, so
  trace-vs-XLA-compile seconds appear in the event once ``CompiledGraph``
  measures them (the stats object is shared, not copied — reads see the
  final timings).

Events also increment ``dl4j_tpu_recompiles_total`` (plus a per-cause
counter) in the default metrics registry and append a ``recompile`` JSONL
event when ``DL4J_TPU_OBS_LOG`` is set.

The ledger counts what call sites tell it. Beside it,
:func:`install_xla_listener` counts every program XLA really builds, where
it is built: a ``jax.monitoring`` listener (installed once, when
``ops/registry.py`` is first imported) gives each one a
``dl4j_tpu_xla_programs_total`` increment and an ``xla_compile`` span whose
parent is the span open on the compiling thread — the request or the
training call that paid for it.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from deeplearning4j_tpu.observe.registry import default_registry, log_event
from deeplearning4j_tpu.observe.tracing import default_tracer

CAUSES = ("first_compile", "new_shape", "graph_mutation",
          "constant_rebind", "variable_rebind", "cache_hit")

_MAX_EVENTS = 2000

# the observe package dir (frames inside it are plumbing, not callsites)
# and the repo root callsites are reported relative to
_OBS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(os.path.dirname(_OBS_DIR))


def _caller_callsite() -> Optional[str]:
    """Repo-relative ``path:line`` of the nearest stack frame OUTSIDE the
    observe package — the source site that registered this compile event.
    graftshape's runtime cross-validation (testing/shapetrace.py) matches
    these against the static registration-site inventory, so the format
    (forward slashes, repo-relative when under the repo) must agree with
    lint ``Finding.path``."""
    f = sys._getframe(1)
    while f is not None:
        fname = f.f_code.co_filename
        if not os.path.abspath(fname).startswith(_OBS_DIR):
            try:
                rel = os.path.relpath(fname, _REPO_ROOT)
            except ValueError:  # different drive (windows) — keep absolute
                rel = fname
            if rel.startswith(".."):
                rel = fname
            return f"{rel.replace(os.sep, '/')}:{f.f_lineno}"
        f = f.f_back
    return None


@dataclasses.dataclass
class CompileEvent:
    seq: int
    graph: str            # model identity ("samediff", "mln", "graph", ...)
    key: str              # cached-function kind ("exec", "train", ...)
    signature: str        # input shape/dtype signature
    cause: str
    timestamp: float      # epoch seconds (display only; never subtracted)
    stats: Any = None     # OptimizeStats (live reference) or None
    callsite: Optional[str] = None  # "path:line" of the registering site

    def to_dict(self) -> Dict[str, Any]:
        out = {"seq": self.seq, "graph": self.graph, "key": self.key,
               "signature": self.signature, "cause": self.cause,
               "timestamp": self.timestamp, "callsite": self.callsite}
        st = self.stats
        if st is not None:
            out["trace_seconds"] = getattr(st, "trace_seconds", None)
            out["compile_seconds"] = getattr(st, "compile_seconds", None)
            out["optimize_seconds"] = getattr(st, "optimize_seconds", None)
            out["nodes_before"] = getattr(st, "nodes_before", None)
            out["nodes_after"] = getattr(st, "nodes_after", None)
            # fusion-tier hits (docs/OPTIMIZER.md § Fusion tier) — lets
            # `tools/obsreport.py --log` show fusion counts per compile
            fusions = getattr(st, "fusions", None)
            if fusions:
                out["fusions"] = dict(fusions)
        return out


class RecompileLedger:
    """Bounded, thread-safe event log of compilations."""

    def __init__(self, max_events: int = _MAX_EVENTS):
        self._events: "deque[CompileEvent]" = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, *, graph: str, key: str, signature: str, cause: str,
               stats: Any = None,
               callsite: Optional[str] = None) -> CompileEvent:
        if cause not in CAUSES:
            raise ValueError(f"unknown recompile cause '{cause}'; "
                             f"valid: {list(CAUSES)}")
        if callsite is None:
            callsite = _caller_callsite()
        with self._lock:
            self._seq += 1
            ev = CompileEvent(seq=self._seq, graph=graph, key=key,
                              signature=signature, cause=cause,
                              timestamp=time.time(), stats=stats,
                              callsite=callsite)
            self._events.append(ev)
        m = default_registry()
        m.counter("dl4j_tpu_recompiles_total").inc()
        m.counter("dl4j_tpu_recompile_cause_total", cause=cause).inc()
        fields = {"graph": graph, "key": key, "signature": signature,
                  "cause": cause, "callsite": callsite}
        fusions = getattr(stats, "fusions", None) if stats is not None \
            else None
        if fusions:
            # fusion-tier hits join the JSONL event so obsreport --log can
            # report them per compile (docs/OPTIMIZER.md § Fusion tier)
            fields["fusions"] = dict(fusions)
        log_event("recompile", **fields)
        return ev

    def events(self) -> Tuple[CompileEvent, ...]:
        with self._lock:
            return tuple(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def summary(self) -> Dict[str, Any]:
        evs = self.events()
        by_cause: Dict[str, int] = {}
        by_callsite: Dict[str, int] = {}
        for ev in evs:
            by_cause[ev.cause] = by_cause.get(ev.cause, 0) + 1
            cs = ev.callsite or "<unknown>"
            by_callsite[cs] = by_callsite.get(cs, 0) + 1
        compile_s = [getattr(ev.stats, "compile_seconds", None)
                     for ev in evs if ev.stats is not None]
        compile_s = [s for s in compile_s if s is not None]
        return {"total": len(evs), "by_cause": by_cause,
                "by_callsite": by_callsite,
                "compile_seconds_sum": round(sum(compile_s), 4)
                if compile_s else None}


_DEFAULT: Optional[RecompileLedger] = None
_DEFAULT_LOCK = threading.Lock()


def default_ledger() -> RecompileLedger:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = RecompileLedger()
        return _DEFAULT


def reset_default_ledger() -> RecompileLedger:
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = None
    return default_ledger()


# ---------------------------------------------------------------------------
# helpers the runtimes call
# ---------------------------------------------------------------------------


def signature_of(*arrays: Any, **named: Any) -> str:
    """Compact shape/dtype signature of a feed set, e.g.
    ``x:f32[32,128],y:f32[32,10]``. Accepts positional arrays (labelled by
    position) and/or name->array pairs; None entries are skipped."""
    import numpy as np

    parts = []
    items = [(str(i), a) for i, a in enumerate(arrays)]
    items += sorted(named.items())
    for name, a in items:
        if a is None:
            continue
        dt = np.dtype(getattr(a, "dtype", type(a))).name \
            if hasattr(a, "dtype") else type(a).__name__
        shape = ",".join(str(int(d)) for d in getattr(a, "shape", ()))
        parts.append(f"{name}:{dt}[{shape}]")
    return "|".join(parts)


def note_jit_signature(fn: Any, *, graph: str, key: str, signature: str,
                       stats: Any = None,
                       cause_if_new_fn: str = "first_compile",
                       callsite: Optional[str] = None) -> Optional[str]:
    """Record a compile event iff ``signature`` is new for ``fn``.

    The seen-signature set rides ON the cached function object, so the
    exact cache-invalidation paths that drop the function also drop its
    history — a rebuilt fn reports ``cause_if_new_fn`` (the invalidation
    cause), a cached fn seeing a fresh signature reports ``new_shape``
    (jax retraces per shape under the hood). Two attributes set by the
    AOT export layer (autodiff/export.py) override those causes:
    ``fn._aot_restored`` marks a fn deserialized from the persistent
    export cache — every event it produces is a ``cache_hit``, not a
    fresh compile; ``fn._aot_polymorphic`` marks a symbolic-batch-dim
    executable — a fresh signature is served by the SAME executable
    without a retrace, so it too records ``cache_hit`` instead of
    ``new_shape``. ``stats`` is attached only to
    the new-fn event: a new_shape retrace never re-ran the optimizer, so
    inheriting the original compile's OptimizeStats would double-count its
    trace/compile seconds in ledger summaries. ``callsite`` defaults to
    the nearest caller frame outside the observe package — the source
    site graftshape's shapetrace attributes the event to. Returns the
    cause recorded, or None on a plain cache hit."""
    try:
        sigs = fn._obs_sigs
    except AttributeError:
        try:
            fn._obs_sigs = sigs = set()
        except (AttributeError, TypeError):
            return None  # fn refuses attributes; skip tracking, never fail
    if signature in sigs:
        return None
    new_fn = not sigs
    restored = getattr(fn, "_aot_restored", False)
    if new_fn:
        cause = "cache_hit" if restored else cause_if_new_fn
    else:
        cause = ("cache_hit"
                 if restored or getattr(fn, "_aot_polymorphic", False)
                 else "new_shape")
    sigs.add(signature)
    if callsite is None:
        # resolved HERE (not in record) so the cache-hit fast path above
        # never pays the stack walk
        callsite = _caller_callsite()
    default_ledger().record(graph=graph, key=key, signature=signature,
                            cause=cause, stats=stats if new_fn else None,
                            callsite=callsite)
    return cause


# ---------------------------------------------------------------------------
# every program XLA builds, counted where it is built
# ---------------------------------------------------------------------------

# jax 0.9 emits BACKEND_COMPILE once per program around
# ``compile_or_get_cached`` — persistent cache warm or cold, so the count is
# the same either way — and CACHE_RETRIEVAL inside it, just before, where the
# executable came from the cache
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_xla_listening = False
_xla_thread = threading.local()


def _on_xla_event(event: str, duration: float, **_kw: Any) -> None:
    if event == _CACHE_RETRIEVAL:
        _xla_thread.retrieved = True
        default_registry().histogram(
            "dl4j_tpu_xla_cache_retrieval_seconds").observe(duration)
    elif event == _BACKEND_COMPILE:
        cached = getattr(_xla_thread, "retrieved", False)
        _xla_thread.retrieved = False
        m = default_registry()
        m.counter("dl4j_tpu_xla_programs_total").inc()
        if not cached:
            m.histogram("dl4j_tpu_xla_compile_seconds").observe(duration)
        tr = default_tracer()
        now = time.perf_counter()
        tr.complete_between("xla_compile", now - duration, now,
                            category="compile", parent=tr.current(),
                            cached=cached)


def install_xla_listener() -> None:
    """Register the listener with ``jax.monitoring`` (idempotent; the caller
    has imported jax already). jax calls it on the compiling thread."""
    global _xla_listening
    with _DEFAULT_LOCK:
        if _xla_listening:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_xla_event)
        _xla_listening = True
