"""What the readers of the host's accounting share: the program's spans that
own the host's time (``serving_step``, ``serving_decode_read``,
``serving_prefill_read``, ``fit_scanned`` and its two stages) carry what the
thread did while each was open: ``cpu_ms`` (on a core) and ``gc_ms`` (the
collector on that thread); a pass of the collector's generation 1 or 2 is a
``host_gc`` span of its own, on whichever thread it ran, with
``callbacks_ms``, the time the other ``gc.callbacks`` (jax's
``collect_garbage``) took around it (docs/OBSERVABILITY.md § Host
accounting).

A program whose spans carry no such args (an older commit), or a
tracer that dropped events, reads as nothing; nothing here raises."""

from typing import Any, Dict, List, Optional, Sequence

import common

spans_of = common.module("layer_metrics", "program_spans")
device_idle = common.module("layer_metrics", "device_idle")

ACCOUNTING = ("cpu_ms", "gc_ms")


def accounted(spans: Sequence[Dict[str, Any]], name: str
              ) -> Optional[List[Dict[str, Any]]]:
    """The spans named ``name``, where there are some, every one carries the
    accounting and the tracer dropped nothing; else None."""
    found = [s for s in spans if s["name"] == name]
    if not found or spans_of.dropped():
        return None
    if any(k not in s["args"] for s in found for k in ACCOUNTING):
        return None
    return found


def reads_below(span, kids) -> List[Dict[str, Any]]:
    """The ``*_read`` spans among ``span``'s descendants: the host blocked
    on the device's answer."""
    return [s for s in spans_of.descendants(span, kids)
            if s["name"].endswith("_read")]


def inside(spans, window) -> List[Dict[str, Any]]:
    """The spans that start inside ``window`` (two ``perf_counter``
    readings); none where there is no window."""
    if not window:
        return []
    t0, t1 = window
    return [s for s in spans if t0 <= s["start"] <= t1]


def overlapping(spans, span, names) -> List[Dict[str, Any]]:
    """The spans named in ``names`` that share some time with ``span``, on
    any thread."""
    t0, t1 = span["start"], span["start"] + span["seconds"]
    return [s for s in spans if s["name"] in names
            and s["start"] < t1 and s["start"] + s["seconds"] > t0]


def usage(span) -> str:
    a = span["args"]
    return " ".join(f"{k} {a[k]}" for k in ACCOUNTING if k in a)


def described(span) -> str:
    """``name ms`` with its accounting (or a collector pass's generation
    and the other callbacks' time around it, or a compile's ``cached``),
    where it has them."""
    a = span["args"]
    more = usage(span)
    if span["name"] == "host_gc":
        more = (f"generation {a.get('generation')} thread {a.get('thread')} "
                f"collected {a.get('collected')} callbacks_ms "
                f"{a.get('callbacks_ms')}")
    elif span["name"] == "xla_compile":
        more = f"cached {a.get('cached')}"
    return (f"{span['name']} {spans_of.ms(span['seconds'])} ms"
            + (f" ({more})" if more else ""))


def idle_note(ctx, suffix: str) -> str:
    """The traced run's device idle share, as ``device_idle_pct`` reads
    it."""
    idle = device_idle.read(ctx)
    return (f"device_idle_pct.{suffix} {idle:.4f}" if idle is not None
            else f"device_idle_pct.{suffix} not read")
