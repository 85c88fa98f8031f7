"""Process-wide metrics registry — counters, gauges, streaming histograms.

The production-serving questions the ROADMAP asks (how many recompiles did
diverse traffic trigger, what are the serving p50/p99 latencies, where did a
slow step go) all reduce to three instrument kinds:

``Counter``    monotonically increasing totals (steps, requests, recompiles).
``Gauge``      last-written level (queue depth, examples/sec).
``Histogram``  streaming distribution with p50/p95/p99 quantiles over
               log-spaced buckets — bounded memory, thread-safe, and
               renderable as a Prometheus cumulative-``le`` histogram.

One process-wide default registry (:func:`default_registry`) is the metric
model every hot layer writes into (SameDiff/MultiLayerNetwork/
ComputationGraph fit, the recompile ledger, ``ParallelInference`` serving);
``ui/server.py`` serves it at ``/metrics`` in Prometheus text format and
``tools/obsreport.py`` summarizes it. All instruments are safe to write from
any thread: one registry lock guards instrument creation, a per-instrument
lock guards updates (serving clients record latencies concurrently).

Naming follows the Prometheus convention: ``dl4j_tpu_<what>_<unit>`` with
``_total`` for counters. Labels are a small dict rendered as
``name{k="v"}``; instruments are keyed by (name, sorted labels).
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

# Default latency bucket bounds (seconds): log-spaced from 100µs to ~56min
# (26 power-of-2 buckets, ~3.3 per decade) — honest p99s on sub-ms serving
# latencies AND multi-minute compile times in one scheme.
_DEFAULT_BOUNDS: Tuple[float, ...] = tuple(
    round(1e-4 * (2.0 ** k), 10) for k in range(26))


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + inner + "}"


class Counter:
    """Monotonic counter (Prometheus ``counter``)."""

    kind = "counter"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...] = ()):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up; use a Gauge")
        with self._lock:
            self.value += amount

    def snapshot(self) -> Dict[str, Any]:
        return {"kind": self.kind, "value": self.value}

    def render(self) -> List[str]:
        v = self.value
        return [f"{self.name}{_render_labels(self.labels)} "
                f"{int(v) if float(v).is_integer() else v}"]


class Gauge(Counter):
    """Last-written level (Prometheus ``gauge``)."""

    kind = "gauge"

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class Histogram:
    """Streaming histogram with quantile estimation.

    Observations land in log-spaced buckets (cumulative-``le`` on render,
    the Prometheus histogram contract); quantiles interpolate linearly
    inside the owning bucket, which bounds the error by the bucket ratio
    (2× by default) — the standard Prometheus ``histogram_quantile``
    trade-off, with bounded memory and O(#buckets) reads."""

    kind = "histogram"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...] = (),
                 bounds: Optional[Sequence[float]] = None):
        self.name = name
        self.labels = labels
        self.bounds: Tuple[float, ...] = tuple(bounds) if bounds is not None \
            else _DEFAULT_BOUNDS
        self.counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        if math.isnan(v):
            return
        # bisect by hand: bounds are tiny (26) and this avoids an import
        lo, hi = 0, len(self.bounds)
        while lo < hi:
            mid = (lo + hi) // 2
            if v <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        with self._lock:
            self.counts[lo] += 1
            self.count += 1
            self.sum += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (q in [0, 1]); None when empty."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
            vmin, vmax = self.min, self.max
        if not total:
            return None
        rank = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            if not c:
                continue
            if cum + c >= rank:
                lo = self.bounds[i - 1] if i > 0 else min(
                    vmin if vmin is not None else 0.0, self.bounds[0])
                hi = self.bounds[i] if i < len(self.bounds) else (
                    vmax if vmax is not None else self.bounds[-1])
                frac = (rank - cum) / c
                return lo + (hi - lo) * max(0.0, min(1.0, frac))
            cum += c
        return vmax

    @property
    def mean(self) -> Optional[float]:
        return (self.sum / self.count) if self.count else None

    def percentiles(self) -> Dict[str, Optional[float]]:
        return {"p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"kind": self.kind, "count": self.count,
                               "sum": self.sum, "min": self.min,
                               "max": self.max, "mean": self.mean}
        out.update(self.percentiles())
        return out

    def render(self) -> List[str]:
        base = dict(self.labels)
        lines: List[str] = []
        cum = 0
        with self._lock:
            counts = list(self.counts)
            count, total = self.count, self.sum
        for bound, c in zip(self.bounds, counts):
            cum += c
            lbl = _render_labels(_label_key({**base, "le": repr(bound)}))
            lines.append(f"{self.name}_bucket{lbl} {cum}")
        lbl = _render_labels(_label_key({**base, "le": "+Inf"}))
        lines.append(f"{self.name}_bucket{lbl} {count}")
        plain = _render_labels(self.labels)
        lines.append(f"{self.name}_sum{plain} {total}")
        lines.append(f"{self.name}_count{plain} {count}")
        return lines


class MetricsRegistry:
    """Instrument container: create-or-get by (name, labels), render all."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                                Counter] = {}

    def _get(self, cls, name: str, labels: Dict[str, str], **kw):
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                inst = cls(name, key[1], **kw)
                self._instruments[key] = inst
            elif type(inst) is not cls:
                # exact-type check: isinstance would hand a Gauge to a
                # counter() caller (Gauge subclasses Counter), silently
                # dropping monotonicity enforcement
                raise TypeError(
                    f"metric '{name}' already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}")
        return inst

    def counter(self, name: str, **labels: str) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels: str) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str,
                  bounds: Optional[Sequence[float]] = None,
                  **labels: str) -> Histogram:
        return self._get(Histogram, name, labels, bounds=bounds)

    def instruments(self) -> List[Counter]:
        with self._lock:
            return list(self._instruments.values())

    def family_total(self, name: str) -> float:
        """Sum of a counter/gauge family across ALL label sets (e.g. the
        per-model ``dl4j_tpu_train_steps_total`` counters)."""
        return sum(i.value for i in self.instruments()
                   if i.name == name and not isinstance(i, Histogram))

    def merged_histogram(self, name: str) -> Histogram:
        """A synthetic histogram merging every label set of ``name`` —
        the cross-model latency distribution summaries read."""
        out: Optional[Histogram] = None
        for inst in self.instruments():
            if inst.name != name or not isinstance(inst, Histogram):
                continue
            if out is None:
                out = Histogram(name, bounds=inst.bounds)
            if inst.bounds != out.bounds:
                continue  # families share bounds; a stray mismatch is skipped
            with inst._lock:
                counts = list(inst.counts)
                c, s, mn, mx = inst.count, inst.sum, inst.min, inst.max
            for i, v in enumerate(counts):
                out.counts[i] += v
            out.count += c
            out.sum += s
            if mn is not None:
                out.min = mn if out.min is None else min(out.min, mn)
            if mx is not None:
                out.max = mx if out.max is None else max(out.max, mx)
        return out if out is not None else Histogram(name)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able {rendered-name: instrument snapshot}."""
        out: Dict[str, Any] = {}
        for inst in self.instruments():
            out[f"{inst.name}{_render_labels(inst.labels)}"] = inst.snapshot()
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition (one ``# TYPE`` line per family)."""
        by_name: Dict[str, List[Counter]] = {}
        for inst in self.instruments():
            by_name.setdefault(inst.name, []).append(inst)
        lines: List[str] = []
        for name in sorted(by_name):
            family = by_name[name]
            lines.append(f"# TYPE {name} {family[0].kind}")
            for inst in sorted(family, key=lambda i: i.labels):
                lines.extend(inst.render())
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# process-wide default registry + JSONL event log
# ---------------------------------------------------------------------------

_DEFAULT: Optional[MetricsRegistry] = None
_DEFAULT_LOCK = threading.Lock()

# the metric catalog every build exposes, registered eagerly so /metrics
# and snapshots always carry the names (zero-valued until traffic arrives)
_CORE_METRICS: Tuple[Tuple[str, str], ...] = (
    ("counter", "dl4j_tpu_recompiles_total"),
    # every program XLA builds, from jax.monitoring (observe/ledger.py)
    ("counter", "dl4j_tpu_xla_programs_total"),
    ("histogram", "dl4j_tpu_xla_cache_retrieval_seconds"),
    ("counter", "dl4j_tpu_train_steps_total"),
    ("counter", "dl4j_tpu_train_examples_total"),
    ("counter", "dl4j_tpu_host_to_device_transfers_total"),
    ("counter", "dl4j_tpu_serving_requests_total"),
    ("counter", "dl4j_tpu_serving_batches_total"),
    ("counter", "dl4j_tpu_serving_rows_total"),
    ("histogram", "dl4j_tpu_train_step_seconds"),
    ("histogram", "dl4j_tpu_serving_request_seconds"),
    ("histogram", "dl4j_tpu_serving_queue_wait_seconds"),
    ("histogram", "dl4j_tpu_serving_batch_seconds"),
    ("histogram", "dl4j_tpu_serving_batch_occupancy"),
    ("gauge", "dl4j_tpu_serving_queue_depth"),
    # generative serving (serving/ — docs/SERVING.md). evicted_total grows
    # reason-labelled children next to this eagerly-registered base.
    ("counter", "dl4j_tpu_serving_admitted_total"),
    ("counter", "dl4j_tpu_serving_evicted_total"),
    ("counter", "dl4j_tpu_serving_generated_tokens_total"),
    ("gauge", "dl4j_tpu_serving_slot_occupancy"),
    ("histogram", "dl4j_tpu_serving_decode_step_seconds"),
    ("histogram", "dl4j_tpu_serving_ttft_seconds"),
    ("histogram", "dl4j_tpu_serving_intertoken_seconds"),
    # robustness tier (faults/ + the engine supervisor + durable
    # checkpoints — docs/ROBUSTNESS.md). faults_injected_total grows
    # point-labelled children next to this eagerly-registered base.
    ("counter", "dl4j_tpu_faults_injected_total"),
    ("counter", "dl4j_tpu_serving_engine_restarts_total"),
    ("counter", "dl4j_tpu_serving_retries_total"),
    ("gauge", "dl4j_tpu_serving_stopped_cleanly"),
    ("counter", "dl4j_tpu_checkpoint_saves_total"),
    ("counter", "dl4j_tpu_checkpoint_corrupt_total"),
    ("counter", "dl4j_tpu_checkpoint_fallback_total"),
    # preemption-proof training (parallel/checkpoint.py async writer +
    # parallel/supervisor.py — docs/ROBUSTNESS.md § Preemption-proof
    # training)
    ("counter", "dl4j_tpu_ckpt_async_saves_total"),
    ("counter", "dl4j_tpu_ckpt_dropped_total"),
    ("counter", "dl4j_tpu_ckpt_blocked_total"),
    ("counter", "dl4j_tpu_ckpt_resumes_total"),
    ("counter", "dl4j_tpu_train_preemptions_total"),
    ("gauge", "dl4j_tpu_ckpt_queue_depth"),
    ("histogram", "dl4j_tpu_ckpt_write_seconds"),
    # SLO admission frontend (serving/frontend.py — docs/SERVING.md).
    # admitted/shed/degraded/transitions grow labelled children
    # ({class}, {class,reason}, {to}) next to these eagerly-registered
    # bases; the state gauge carries the OVERLOAD_STATES index.
    ("gauge", "dl4j_tpu_slo_state"),
    ("gauge", "dl4j_tpu_slo_breaker_open"),
    ("counter", "dl4j_tpu_slo_admitted_total"),
    ("counter", "dl4j_tpu_slo_shed_total"),
    ("counter", "dl4j_tpu_slo_degraded_total"),
    ("counter", "dl4j_tpu_slo_transitions_total"),
)


def default_registry() -> MetricsRegistry:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = MetricsRegistry()
            for kind, name in _CORE_METRICS:
                getattr(_DEFAULT, kind)(name)
        return _DEFAULT


def reset_default_registry() -> MetricsRegistry:
    """Drop every instrument and start a fresh default registry (tests)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = None
    return default_registry()


OBS_LOG_ENV = "DL4J_TPU_OBS_LOG"

_LOG_LOCK = threading.Lock()
# paths whose writes failed: logging to them is DISABLED (with one warning
# per path) — an unwritable log or a full disk must cost one syscall per
# event forever after, not an exception inside a training/serving loop
_LOG_FAILED_PATHS: set = set()


def reset_log_state() -> None:
    """Forget failed JSONL log paths (tests; or after freeing disk)."""
    with _LOG_LOCK:
        _LOG_FAILED_PATHS.clear()


def log_event(kind: str, **fields: Any) -> None:
    """Append one JSONL event to the ``DL4J_TPU_OBS_LOG`` file (no-op when
    the env var is unset). Schema: every line is a JSON object with ``ts``
    (epoch seconds — a timestamp, not a duration), ``kind``, plus the
    kind-specific fields (docs/OBSERVABILITY.md).

    Failure policy: a path that cannot be written (bad path, permissions,
    disk full mid-run) warns ONCE and disables logging to that path for
    the rest of the process — observability must never take down the
    training/serving loop it observes. Pointing the env var at a fresh
    path (or :func:`reset_log_state`) re-enables logging."""
    path = os.environ.get(OBS_LOG_ENV)
    if not path or path in _LOG_FAILED_PATHS:
        return
    rec = {"ts": round(time.time(), 6), "kind": kind}
    rec.update(fields)
    try:
        line = json.dumps(rec, default=str)
    except (TypeError, ValueError):
        line = json.dumps({"ts": rec["ts"], "kind": kind,
                           "error": "unserializable event"})
    try:
        with _LOG_LOCK, open(path, "a", encoding="utf-8") as fh:
            fh.write(line + "\n")
    except OSError as e:
        with _LOG_LOCK:
            first = path not in _LOG_FAILED_PATHS
            _LOG_FAILED_PATHS.add(path)
        if first:
            logger.warning(
                "%s: cannot write %s (%s) — JSONL event logging DISABLED "
                "for this path for the rest of the process", OBS_LOG_ENV,
                path, e)
