"""From a profiler trace to numbers: busy time, kernel time, the largest
device operations and the longest idle gaps.

The reduction works on a plain form, so that a small recorded trace (a JSON
file) tests it without a chip:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host":    [[name, start_ns, dur_ns], ...]}

``devices`` holds the events of each device plane's operations line; on that
line a control-flow operation (``while``, a call) covers its children, so
time per name is SELF time: an event's duration less what its direct
children cover. ``host`` holds the benchmark's own annotations (and the
program's spans moved onto the trace's clock), which label idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"
SHORT_GAP_NS = 20_000
SHORT_GAP_LABEL = "under_20us_between_device_ops"

Event = Tuple[str, int, int]


# host events kept: the benchmark's annotations, and what jax itself marks on
# the calling thread (the dispatch of a jitted call, the wait for a result)
HOST_PREFIXES = ("bench_", "PjitFunction(", "np.asarray(", "DevicePut")


def load_xplane(trace_dir: str, host_prefixes: Sequence[str] = HOST_PREFIXES
                ) -> Dict[str, Any]:
    """Read the newest ``*.xplane.pb`` under ``trace_dir`` into the plain
    form. Host events are kept where their name starts with one of
    ``host_prefixes``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    return {"devices": devices, "host": host}


def merged_intervals(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Union of the events' intervals, sorted, as (start, end)."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[Tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event],
               counts: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Summed self time per name on one line: duration less the direct
    children's (events nested inside it on the same line). ``counts``, where
    given, receives the number of events per name."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    total: Dict[str, int] = {}
    if counts is not None:
        for name, _, _ in order:
            counts[name] = counts.get(name, 0) + 1
    stack: List[List[Any]] = []  # [name, end, self]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0) + max(own, 0)

    for name, start, dur in order:
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return total


def clip(events: Iterable[Event], start: int, end: int) -> List[Event]:
    out = []
    for name, s, d in events:
        s2, e2 = max(s, start), min(s + d, end)
        if e2 > s2:
            out.append((name, s2, e2 - s2))
    return out


def window_of(trace: Dict[str, Any], marker: str = "bench_window"
              ) -> Optional[Tuple[int, int]]:
    """The traced window: the benchmark's ``bench_window`` annotation where
    the trace holds it, else from the first to the last device event."""
    marks = [(s, s + d) for n, s, d in trace["host"] if n == marker]
    if marks:
        return max(marks, key=lambda m: m[1] - m[0])
    evs = [ev for plane in trace["devices"].values() for ev in plane]
    if not evs:
        return None
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def label_at(host: Sequence[Event], start: int, end: int,
             default: str) -> str:
    """The innermost host annotation (other than the window's) that covers
    most of [start, end)."""
    best, best_cover, best_len = default, 0, 1 << 62
    for name, s, d in host:
        if name == "bench_window":
            continue
        cover = min(end, s + d) - max(start, s)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def reduce_trace(trace: Dict[str, Any], *, top: int = 10,
                 gap_default: str = "no_annotation") -> Dict[str, Any]:
    """busy_s and window_s (averaged over the device planes), self time per
    operation name, the ``top`` operations and the ``top`` idle gaps."""
    window = window_of(trace)
    planes = trace["devices"]
    if window is None or not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "op_seconds": {},
                "op_counts": {}, "device_ops": [], "idle_gaps": [],
                "planes": 0}
    w0, w1 = window
    busy_ns = 0
    op_ns: Dict[str, int] = {}
    op_counts: Dict[str, int] = {}
    gaps: List[Tuple[int, int]] = []
    for events in planes.values():
        inside = clip(events, w0, w1)
        merged = merged_intervals(inside)
        busy_ns += sum(e - s for s, e in merged)
        for name, ns in self_times(inside, op_counts).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if w1 > edge:
            gaps.append((edge, w1))
    n = len(planes)
    by_label: Dict[str, int] = {}
    for s, e in gaps:
        # the pauses between two operations of one program are many and
        # tiny: they are the device's own, not the host's
        label = (SHORT_GAP_LABEL if e - s < SHORT_GAP_NS
                 else label_at(trace["host"], s, e, gap_default))
        by_label[label] = by_label.get(label, 0) + (e - s)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "planes": n,
        "op_seconds": {k: v / n / 1e9 for k, v in op_ns.items()},
        "op_counts": {k: v / n for k, v in op_counts.items()},
        "device_ops": [[short_name(k), v / n / 1e9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        # the idle time by what the host was doing, largest first; and the
        # single longest gaps, for the reader of PERF.md
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": [[label_at(trace["host"], s, e, gap_default),
                          (e - s) / 1e9] for s, e in longest],
    }


def short_name(name: str, limit: int = 120) -> str:
    """An operation's name as the trace prints it is the whole HLO line;
    keep the instruction, its first result's type and a custom call's
    target."""
    m = re.match(r"(%[^ ]+) = (\(?[a-z0-9]+\[[^\]]*\])?", name)
    head = (m.group(1) + (" " + m.group(2) if m.group(2) else "")
            if m else name)
    t = re.search(r'custom_call_target="([^"]+)"', name)
    if t:
        head += " " + t.group(1)
    return head[:limit]


def matching(tr: Dict[str, Any], all_of: Sequence[str]
             ) -> Optional[Tuple[float, float]]:
    """(summed self seconds, number of events) of the operations whose name
    holds every string of ``all_of``; None where there is none (a reader
    then returns nothing)."""
    names = [k for k in tr["op_seconds"] if all(p in k for p in all_of)]
    if not names:
        return None
    return (sum(tr["op_seconds"][k] for k in names),
            sum(tr["op_counts"][k] for k in names))
