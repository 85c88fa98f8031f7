"""Lightweight span tracer — ONE trace format for the whole runtime.

Chrome trace-event JSON (``chrome://tracing`` / Perfetto) was already the
profiling artifact (``utils/profiling.py``); this module owns the format now
and ``ChromeTraceWriter`` there subclasses :class:`SpanTracer`, so spans
recorded by the training loop, the compile path, and the serving loop land
in the same timeline as the listener-driven per-iteration events.

Clocks are monotonic (``time.perf_counter``) — wall-clock (``time.time``)
deltas jump with NTP and are banned for durations (graftlint GL010).
``perf_origin`` is the ``perf_counter`` reading behind ``ts`` 0.

Spans nest: each thread keeps its own stack of open spans and events carry
the thread id as ``tid``, so concurrent serving clients render as separate
tracks. Every span has an ``id`` (one running number for the process) and a
``parent`` (the span open on the same thread when it started, else None);
both ride in the event's ``args``, beside ``request`` where a span belongs
to one serving request, so a reader can put a stage down to its step and a
step's stages down to a request. The event buffer is bounded (newest kept)
— tracing a week-long serving process must not grow host memory without
bound — and ``dropped`` counts what the bound evicted: a reader that sees
``dropped > 0`` holds half a window.

One clock with the device trace: while the body of ``span()`` runs, the
same interval is open as a ``jax.profiler.TraceAnnotation`` named
``dl4j/<name>`` (a no-op when no profiler session is on), so under
``utils/profiling.device_trace`` the program's stages sit on the host track
beside the device's operations. This module never imports jax: the
annotation is looked up only once ``jax`` is already in ``sys.modules``.

Host accounting (``span(..., host=True)``, or ``host_usage``/``host_since``
around an interval recorded after the fact): the spans that own the host's
time carry what the thread did while they were open, as deltas: ``cpu_ms``
(on a core; the rest of the wall time the thread was off it: waiting on the
GIL, a lock or the device, or preempted) and ``gc_ms`` (the collector's
passes on this thread, timed by ``observe/host.py``'s hook).
"""

from __future__ import annotations

import itertools
import json
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

# A reader that finds ``dropped > 0`` holds half a window and reads nothing, so
# the bound has to hold what a reader's window writes. Sized for a serving
# engine at 1.3 times 5000 tokens/s on 32 slots (GPT-2 small on one v5e once
# the loop runs a step ahead of the tokens' read): a 40 s window and 10 s of
# drain are some 10 thousand decode steps of 9-10 events and 5 thousand
# requests of 64 tokens at 9 events, near 140 thousand
# (tests/test_serving_ahead.py holds the arithmetic to the engine's own
# counts). Some 120 MB when full.
_MAX_EVENTS = 262144

# one running number for every span of every tracer in the process
_IDS = itertools.count(1)

_ANNOTATION = None

# collector seconds of each thread (``seconds``), added by the collector's
# hook (``observe/host.py``) as each pass ends on that thread
thread_gc = threading.local()


def _nothing_queued() -> None:
    pass


# writes what the collector's hook queued (``observe/host.py`` sets it when
# the hook is installed). Called only where this thread holds no lock of the
# observe package: the hook runs wherever an allocation starts a pass, so it
# takes none, and queues what it saw for this to write
flush_host: Callable[[], None] = _nothing_queued


def host_usage() -> Tuple[float, float]:
    """This thread's CPU seconds and collector seconds so far."""
    return time.thread_time(), getattr(thread_gc, "seconds", 0.0)


def host_since(start: Tuple[float, float]) -> Dict[str, Any]:
    """The accounting ``args`` of an interval from ``start`` (a
    :func:`host_usage` reading) to now. Read ``start`` after the interval's
    first clock reading and this before its last, so that ``cpu_ms`` lies
    inside the wall time."""
    cpu, gc_s = host_usage()
    return {"cpu_ms": round(1e3 * (cpu - start[0]), 3),
            "gc_ms": round(1e3 * (gc_s - start[1]), 3)}


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` once jax has been imported by someone
    else; None until then (and while jax is only half imported)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        _ANNOTATION = getattr(getattr(jax, "profiler", None),
                              "TraceAnnotation", None)
    return _ANNOTATION


class Span:
    """One open span: the context manager ``SpanTracer.span`` returns. The
    body may add ``args`` known only at the end with :meth:`set`; ``start``
    is the ``perf_counter`` reading the span opened at. With ``host`` the
    span keeps the host's accounting (the module's docstring)."""

    __slots__ = ("_tracer", "name", "category", "args", "id", "parent",
                 "start", "_annotation", "_host", "_since")

    def __init__(self, tracer: "SpanTracer", name: str, category: str,
                 args: Dict[str, Any], host: bool = False):
        self._tracer, self.name, self.category = tracer, name, category
        self.args, self._host = args, host

    def set(self, **args) -> None:
        """Add ``args``. The event of a closed span holds this same dict, so
        what is known only after the span closed (the statistics of a decode
        step that is read a step late) still reaches it."""
        self.args.update(args)

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        self.parent = stack[-1] if stack else None
        self.id = next(_IDS)
        stack.append(self.id)
        cls = _ANNOTATION or _annotation_cls()
        if cls is not None:
            self._annotation = cls("dl4j/" + self.name)
            self._annotation.__enter__()
        else:
            self._annotation = None
        self.start = time.perf_counter()
        if self._host:
            self._since = host_usage()
        return self

    def __exit__(self, *exc) -> bool:
        if self._host:
            self.args.update(host_since(self._since))
        end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tr = self._tracer
        tr._stack().pop()
        args = self.args
        args["id"], args["parent"] = self.id, self.parent
        tr._append({
            "name": self.name, "cat": self.category, "ph": "X",
            "ts": (self.start - tr.perf_origin) * 1e6,
            "dur": (end - self.start) * 1e6, "pid": 0,
            "tid": threading.get_ident() % 1_000_000, "args": args,
        })
        if self._host:
            flush_host()
        return False


class SpanTracer:
    """Nested-span recorder emitting Chrome trace events."""

    def __init__(self, max_events: Optional[int] = _MAX_EVENTS):
        # max_events=None means unbounded (explicit artifact writers);
        # the process-wide default tracer stays bounded, newest kept
        self.events: "deque[Dict[str, Any]]" = deque(maxlen=max_events)
        self.perf_origin = time.perf_counter()
        self.dropped = 0
        self._lock = threading.Lock()
        self._local = threading.local()

    # -- clock ---------------------------------------------------------------
    def _us(self) -> float:
        """Microseconds since tracer start (monotonic)."""
        return (time.perf_counter() - self.perf_origin) * 1e6

    # -- nesting -------------------------------------------------------------
    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = []
            return stack

    def current(self) -> Optional[int]:
        """The ``id`` of the span open on this thread, else None."""
        stack = self._stack()
        return stack[-1] if stack else None

    # -- recording -----------------------------------------------------------
    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.events) == self.events.maxlen:
                self.dropped += 1
            self.events.append(ev)

    def span(self, name: str, category: str = "step", *, host: bool = False,
             **args) -> Span:
        """Record a complete ('X') event around the with-block; ``as`` gives
        the :class:`Span`. Nesting is carried twice: by the ts/dur intervals
        per tid (how the chrome trace viewer reconstructs the stack) and by
        ``args["id"]``/``args["parent"]`` (how a reader does). ``host``: the
        span also carries the host's accounting, and its end writes what
        the collector's hook queued."""
        return Span(self, name, category, args, host)

    def complete(self, name: str, start_us: float, dur_us: float,
                 category: str = "step", parent: Optional[int] = None,
                 tid: Optional[int] = None, **args) -> int:
        """Record an explicit complete event (for externally measured
        intervals, e.g. the AOT trace/compile split) on thread ``tid`` (a
        ``threading.get_ident()``; this thread where None). Returns its
        id."""
        args["id"], args["parent"] = next(_IDS), parent
        if tid is None:
            tid = threading.get_ident()
        self._append({"name": name, "cat": category, "ph": "X",
                      "ts": start_us, "dur": dur_us, "pid": 0,
                      "tid": tid % 1_000_000, "args": args})
        return args["id"]

    def complete_between(self, name: str, perf_start: float, perf_end: float,
                         category: str = "step",
                         parent: Optional[int] = None,
                         tid: Optional[int] = None, **args) -> int:
        """Record a complete event from two ``time.perf_counter()`` readings
        (same monotonic clock as the tracer — no epoch conversion). An
        after-the-fact span has the ``parent`` it is given, else none."""
        return self.complete(name, (perf_start - self.perf_origin) * 1e6,
                             (perf_end - perf_start) * 1e6,
                             category=category, parent=parent, tid=tid,
                             **args)

    def async_between(self, name: str, perf_start: float, perf_end: float,
                      key: int, category: str = "step", **args) -> int:
        """A span that belongs to a request, not to a thread's stack, from
        two ``perf_counter`` readings: Chrome's async pair ('b'/'e', matched
        by ``cat`` and ``id`` = ``key``). Requests overlap on the worker's
        thread, so as 'X' events they would neither nest in a viewer nor
        stay out of the way of a reader that labels a moment by the thread
        spans covering it (the benchmark's idle gaps). The 'b' event carries
        ``dur`` too, so one event reads the whole span. Returns its id."""
        args["id"], args["parent"] = next(_IDS), None
        ts = (perf_start - self.perf_origin) * 1e6
        dur = (perf_end - perf_start) * 1e6
        tid = threading.get_ident() % 1_000_000
        self._append({"name": name, "cat": category, "ph": "b", "id": key,
                      "ts": ts, "dur": dur, "pid": 0, "tid": tid,
                      "args": args})
        self._append({"name": name, "cat": category, "ph": "e", "id": key,
                      "ts": ts + dur, "pid": 0, "tid": tid, "args": {}})
        return args["id"]

    def instant(self, name: str, **args) -> None:
        """A moment: the collector's passes before it are written first."""
        flush_host()
        self._append({"name": name, "cat": "marker", "ph": "i",
                      "ts": self._us(), "pid": 0,
                      "tid": threading.get_ident() % 1_000_000, "s": "g",
                      "args": args})

    # -- export --------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        flush_host()
        with self._lock:
            events = list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        # graftlife: justified(GR005): human-facing trace dump to a
        # caller-chosen path — nothing loads it back; re-run to regenerate
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_dict(), f)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0


_DEFAULT: Optional[SpanTracer] = None
_DEFAULT_LOCK = threading.Lock()


def default_tracer() -> SpanTracer:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = SpanTracer()
        return _DEFAULT


def reset_default_tracer() -> SpanTracer:
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = None
    return default_tracer()
