"""The collector's passes, timed where they happen (docs/OBSERVABILITY.md
§ Host accounting).

``install_gc_hook()`` puts two callbacks around ``gc.callbacks``: one
first and one last, so that a pass is timed without the other callbacks,
which run at its start and at its end (jax's calls XLA's
``collect_garbage`` at both). Every pass adds its seconds to the collecting
thread's ``tracing.thread_gc`` (what a span's ``gc_ms`` reads) and goes to
histogram ``dl4j_tpu_gc_pause_seconds{generation}``; a pass of generation 1
or 2 is also a ``host_gc`` span (``category="host"``; ``generation``,
``collected``, ``thread``, and ``callbacks_ms``: the other callbacks' time
around it; its parent the span open on the collecting thread) and, while a
profiler session is on, a ``dl4j/host_gc`` annotation on that thread's host
line. Generation-0 passes, hundreds a second, get no span.

**The callbacks take no lock.** They run wherever an allocation starts a
pass: inside ``SpanTracer.to_dict()``, which copies the ring under the
tracer's lock, or under the registry's lock, on the same thread. So they
only do thread-local arithmetic and append a tuple to a deque (atomic, and
CPython starts no pass inside a callback); :func:`flush` writes the spans
and the histogram later, where the tracer holds no lock: at the end of a
span that keeps host accounting, at an ``instant`` and at ``to_dict()``.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Dict

from deeplearning4j_tpu.observe import tracing
from deeplearning4j_tpu.observe.registry import default_registry

# 10 us ... 21 s: a generation-0 pass takes tens of microseconds
_BOUNDS = tuple(1e-5 * 2.0 ** k for k in range(22))

# passes timed and not written yet, as (generation, start, end, seconds of
# the other callbacks, collected, thread ident, thread name, parent span):
# newest kept, should nothing write them
_PASSES: deque = deque(maxlen=65536)
_open = threading.local()
_install_lock = threading.Lock()


def _first(phase: str, info: Dict[str, int]) -> None:
    """First of ``gc.callbacks``: before the others at a pass's start, and
    the pass's end at its stop."""
    now = time.perf_counter()
    if phase == "start":
        _open.outer = now
        return
    opened = getattr(_open, "pass_", None)
    if opened is None:  # the hook came in while this pass ran
        return
    _open.pass_ = None
    start, parent, name, annotation = opened
    if annotation is not None:
        annotation.__exit__(None, None, None)
    tracing.thread_gc.seconds = (getattr(tracing.thread_gc, "seconds", 0.0)
                                 + now - start)
    _open.closed = (start, now, parent, name)


def _last(phase: str, info: Dict[str, int]) -> None:
    """Last of ``gc.callbacks``: the pass's start at its start, and after
    the others at its stop."""
    gen = info["generation"]
    if phase == "start":
        annotation = parent = name = None
        if gen:
            tr = tracing._DEFAULT  # the accessor takes a lock; this may not
            parent = tr.current() if tr is not None else None
            # current_thread() takes a lock for a thread threading did not
            # start; the table of those it did is a plain dict
            ident = threading.get_ident()
            thread = threading._active.get(ident)
            name = thread.name if thread is not None else str(ident)
            cls = tracing._ANNOTATION or tracing._annotation_cls()
            if cls is not None:
                annotation = cls("dl4j/host_gc")
                annotation.__enter__()
        _open.pass_ = (time.perf_counter(), parent, name, annotation)
        return
    now = time.perf_counter()
    closed = getattr(_open, "closed", None)
    if closed is None:
        return
    _open.closed = None
    start, end, parent, name = closed
    others = start - getattr(_open, "outer", start) + now - end
    _PASSES.append((gen, start, end, others, info["collected"],
                    threading.get_ident(), name, parent))


def flush() -> None:
    """Write the queued passes to the default tracer and registry."""
    if not _PASSES:
        return
    tr, reg = tracing.default_tracer(), default_registry()
    hists: Dict[int, object] = {}
    while True:
        try:
            gen, start, end, others, collected, ident, name, parent = \
                _PASSES.popleft()
        except IndexError:
            return
        if gen not in hists:
            hists[gen] = reg.histogram("dl4j_tpu_gc_pause_seconds",
                                       bounds=_BOUNDS, generation=str(gen))
        hists[gen].observe(end - start)
        if gen:
            tr.complete_between("host_gc", start, end, category="host",
                                parent=parent, tid=ident, generation=gen,
                                collected=collected, thread=name,
                                callbacks_ms=round(1e3 * others, 3))


def install_gc_hook() -> None:
    """Put the callbacks first and last in ``gc.callbacks``, where they are
    again after any added since (idempotent)."""
    with _install_lock:
        for callback in (_first, _last):
            if callback in gc.callbacks:
                gc.callbacks.remove(callback)
        gc.callbacks.insert(0, _first)
        gc.callbacks.append(_last)
        tracing.flush_host = flush


def reset() -> None:
    """Drop the passes not written yet (test isolation)."""
    _PASSES.clear()
