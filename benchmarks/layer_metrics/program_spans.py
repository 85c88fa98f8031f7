"""What the readers of the program's stage spans share: the spans of the
program's own tracer (``deeplearning4j_tpu.observe``) as name, ``start``
(a ``perf_counter`` reading), ``seconds`` and ``args``, the tree their
``args["id"]``/``args["parent"]`` make, and the rule that a tracer whose
bound evicted events (``dropped > 0``) is read as nothing: a median over
half a window is no reading. A program that has no such spans, as the
commits before PR 25 have not, reads as nothing too, and nothing raises."""

from typing import Any, Dict, Iterable, List, Optional


def tracer():
    from deeplearning4j_tpu import observe

    return observe.tracer()


def dropped() -> int:
    return int(getattr(tracer(), "dropped", 0))


def program_spans(phases: Iterable[str] = ("X",)) -> List[Dict[str, Any]]:
    """The tracer's spans on the host's ``perf_counter`` clock. ``"X"`` is a
    thread's span; ``"b"`` the opening of a request's (an async pair whose
    'b' event carries the duration too)."""
    tr = tracer()
    origin = getattr(tr, "perf_origin", None)
    if origin is None:
        return []
    return [{"name": ev["name"], "start": origin + ev["ts"] / 1e6,
             "seconds": ev["dur"] / 1e6, "args": ev.get("args", {})}
            for ev in tr.to_dict()["traceEvents"]
            if ev.get("ph") in phases and "dur" in ev]


def children_of(spans: Iterable[Dict[str, Any]]
                ) -> Dict[Optional[int], List[Dict[str, Any]]]:
    kids: Dict[Optional[int], List[Dict[str, Any]]] = {}
    for s in spans:
        kids.setdefault(s["args"].get("parent"), []).append(s)
    return kids


def descendants(span: Dict[str, Any], kids) -> List[Dict[str, Any]]:
    out, todo = [], [span]
    while todo:
        for child in kids.get(todo.pop()["args"].get("id"), []):
            out.append(child)
            todo.append(child)
    return out


def ms(seconds: float) -> float:
    return round(1e3 * seconds, 3)
