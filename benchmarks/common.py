"""What every cell shares: the manifest, the device and its peaks, the
statistics, and the result line. Nothing here knows a configuration, a
traffic mix or a metric by name: those are files found by name."""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# scratch of a run (trace files), inside the checkout, git-ignored
OUT_DIR = os.path.join(ROOT, ".bench_out")

# Published peaks of one chip, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
# 16 GB of HBM at 819 GB/s. A kind that is not here is an error, never a
# default (copied from bench.py ``_PEAK_TFLOPS``; see PERF.md).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "memory_bytes": 16e9},
}


class BenchError(RuntimeError):
    """The run cannot be made: exit non-zero and print no result."""


def peaks_of(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise BenchError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"benchmarks/common.py PEAKS with its source") from None


# --------------------------------------------------------------- manifest


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's manifest entry with its configuration and traffic files."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in manifest["configs"]}
    cell["config_entry"] = configs[cell["config"]]
    cell["cfg"] = load_json(os.path.join(root, cell["config_entry"]["file"]))
    cell["mix"] = load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def listed(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if listed(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if listed(m)]
    return cell


_MODULES: Dict[str, Any] = {}


def module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` found by name and loaded by path (a
    metric's name may hold a dot, which no import statement takes)."""
    key = f"{kind}/{name}"
    if key not in _MODULES:
        path = os.path.join(HERE, kind, name + ".py")
        if not os.path.exists(path):
            raise BenchError(f"no file {path}")
        if HERE not in sys.path:
            sys.path.insert(0, HERE)
        spec = importlib.util.spec_from_file_location(
            "bench_" + re.sub(r"[^0-9A-Za-z_]", "_", key), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


# ----------------------------------------------------------------- device


def find_chips(need: int, require_tpu: bool = True):
    """The devices JAX reports, or BenchError where there is no accelerator
    or there are fewer chips than the cell asks for. No CPU fallback."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax reports platform "
                         f"{devs[0].platform!r}")
    if len(devs) < need:
        raise BenchError(f"cell needs {need} chips, jax reports {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says). The program's own switch is used so
    that it sets no other directory in code."""
    from deeplearning4j_tpu.environment import enable_compile_cache as on

    return on()


def memory_peak_bytes(devs: Sequence) -> int:
    """High-water mark on the fullest chip, a running program's temporaries
    included: on the v5e ``peak_bytes_in_use`` counts live arrays only and
    ``peak_bytes_reserved`` matches the compiler (PR 21)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_reserved") or 0),
                   int(stats.get("peak_bytes_in_use") or 0))
    return peak


def device_record(devs: Sequence) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": 0}


# ------------------------------------------------------------- statistics


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) over ALL samples, linear between ranks
    (the arithmetic of bench.py ``_pct``)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


# ------------------------------------------------------------ result line


class Checks:
    """Each number compared, beside its limit. ``correct`` is their AND."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = bool(value == value and value <= limit)  # NaN fails
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit), "ok": ok})

    def require(self, name: str, ok: bool, note: str = "") -> None:
        self.rows.append({"name": name, "value": 0.0 if ok else 1.0,
                          "limit": 0.0, "ok": bool(ok), "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def compared(self) -> Dict[str, Any]:
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}


def emit(*, checks: Checks, attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
         breakdown: Optional[Dict[str, Any]] = None,
         out=sys.stdout, err=sys.stderr) -> Dict[str, Any]:
    line: Dict[str, Any] = {
        "correct": checks.correct, "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = checks.compared()  # comes last
    for r in checks.rows:
        print(f"compared {r['name']}: {r['value']:.6g} limit "
              f"{r['limit']:.6g} {'ok' if r['ok'] else 'FAILED'} "
              f"{r.get('note', '')}".rstrip(), file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return line


def select_metrics(declared: List[Dict[str, Any]],
                   values: Dict[str, Optional[float]]
                   ) -> Dict[str, Dict[str, Any]]:
    """The declared metrics that have a reading, with their units. A metric
    whose reader found nothing is left out of the line."""
    out = {}
    for m in declared:
        v = values.get(m["name"])
        if v is None:
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def read_layer_metrics(cell: Dict[str, Any], ctx: Dict[str, Any]
                       ) -> Dict[str, Optional[float]]:
    """Each per-layer metric is a reader of its own,
    ``layer_metrics/<name>.py`` with ``read(ctx)``; ``<name>.json`` instead
    names a general reader and its parameters."""
    values: Dict[str, Optional[float]] = {}
    for m in cell["per_layer"]:
        name = m["name"]
        spec = os.path.join(HERE, "layer_metrics", name + ".json")
        if os.path.exists(spec):
            params = load_json(spec)
            reader = module("layer_metrics", params["reader"])
            values[name] = reader.read(ctx, **params.get("args", {}))
        else:
            values[name] = module("layer_metrics", name).read(ctx)
    return values


# the process's start, taken when run.py first imports this module
START = time.perf_counter()
