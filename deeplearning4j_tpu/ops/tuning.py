"""Kernel autotuner + measured dispatch tables (docs/KERNELS.md).

The platform-helper table (``ops/registry.py``) picks kernels by *backend*;
the right unit is *(device kind, op, shape bucket)*: where a Pallas kernel
overtakes XLA depends on the chip and the length, so one hardcoded
``FLASH_MIN_T_DEFAULT`` cannot serve both a v5e and a v5p (the sweep that
first showed a crossover ran on the 2026-07 rig, whose numbers are void:
``ROADMAP.md`` D15). This module owns

* the **tuning table**: a JSON document keyed on device kind holding, per
  op, pallas-vs-XLA crossover thresholds and per-shape-bucket Pallas block
  sizes. Dispatch reads the checked-in tables (``ops/tuning_tables/``:
  ``default.json``, then ``<device_kind>.json`` when one was committed) and
  nothing else, so every machine with the same checkout dispatches alike.
  Only an explicit ``DL4J_TPU_TUNING_DIR`` adds a measured overlay from
  that directory; ``DL4J_TPU_*`` env overrides (read by the dispatch
  sites) still win.
* the **autotuner** (:func:`autotune`): times candidate configurations with
  AOT lowering — ``jax.jit(fn).lower(*args).compile()`` — so measurement
  runs never contaminate the process jit cache (the SNIPPETS AOT idiom),
  and persists the winners — to ``DL4J_TPU_TUNING_DIR`` when set, else to
  the git-ignored ``.tuning/`` at the root of the checkout, from where a
  table is committed by copying it under ``ops/tuning_tables/``.
  ``tools/tune.py`` is the CLI;
  ``make tune-smoke`` runs a tiny-shape pass that must exit 0 anywhere.
* the **dispatch feed**: ``flash_min_t()``, the Pallas block pickers in
  ``pallas_attention``/``pallas_matmul``/``quantized``,
  and the ``usable()`` gates consult :func:`tuned` so resolve decisions are
  measured, not guessed. Decisions are visible in the
  ``dl4j_tpu_helper_dispatch_total{op,impl,reason}`` counter family.

Schema (one document per device kind)::

    {"schema": "dl4j_tpu_tuning_v1",
     "device_kind": "cpu",
     "entries": {
       "dot_product_attention": {
         "flash_min_t": 4096,
         "blocks": {"t4096": {"block_q": 512, "block_k": 512}}},
       "fused_matmul_bias_act": {
         "pallas_min_m": 8,
         "blocks": {"m512_k512_n512": {"block_m": 256, ...}}},
       ...}}

Fragments emitted by ``tools/bench_attention_sweep.py`` use the same schema
and merge into the committed default table via :meth:`TuningTable.merge`.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.environment import CHECKOUT_ROOT

logger = logging.getLogger(__name__)

SCHEMA = "dl4j_tpu_tuning_v1"
ENV_DIR = "DL4J_TPU_TUNING_DIR"

_PACKAGE_TABLE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "tuning_tables")
# where the autotuner writes when DL4J_TPU_TUNING_DIR is unset
_CHECKOUT_TUNING_DIR = os.path.join(CHECKOUT_ROOT, ".tuning")

# memoized per-device-kind merged tables + once-only warnings for corrupt
# files; reset_tables() is the test seam and runs after autotune() saves
_ACTIVE: Dict[str, "TuningTable"] = {}
_WARNED_PATHS: set = set()
_RESET_CALLBACKS: List[Callable[[], None]] = []


# ---------------------------------------------------------------------------
# keys: device kinds and shape buckets
# ---------------------------------------------------------------------------


def normalize_device_kind(kind: str) -> str:
    """``'TPU v5 lite'`` -> ``'tpu_v5_lite'`` — filesystem- and JSON-safe."""
    return re.sub(r"[^a-z0-9]+", "_", str(kind).strip().lower()).strip("_") \
        or "unknown"


def current_device_kind() -> str:
    """Device kind of the device computation will actually target — honors
    an enclosing ``jax.default_device(...)`` scope like
    ``registry.current_platform`` does."""
    import jax

    dev = jax.config.jax_default_device
    if dev is not None and getattr(dev, "device_kind", None):
        return normalize_device_kind(dev.device_kind)
    # a backend that cannot list its devices raises here: there is no
    # device kind to dispatch for
    return normalize_device_kind(jax.devices()[0].device_kind)


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (>= 1) — the shape-bucket unit. Kernel
    timing varies smoothly inside a 2× band; per-exact-shape entries would
    never generalize past the bench shapes."""
    n = max(int(n), 1)
    p = 1
    while p < n:
        p *= 2
    return p


def bucket_t(t: int) -> str:
    """Sequence-length bucket for attention-shaped ops."""
    return f"t{pow2_bucket(t)}"


def bucket_mkn(m: int, k: int, n: int) -> str:
    """(M, K, N) bucket for matmul-shaped ops."""
    return f"m{pow2_bucket(m)}_k{pow2_bucket(k)}_n{pow2_bucket(n)}"


def bucket_rows(rows: int) -> str:
    """Row-count bucket for row-parallel elementwise kernels (LayerNorm,
    the fused updater step)."""
    return f"r{pow2_bucket(rows)}"


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TuningTable:
    """One device kind's measured dispatch configuration."""

    device_kind: str
    entries: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    source: str = ""
    # every file merged into this table, in merge order (active_table fills
    # it; chip_smoke.py prints it so a run says what steered its dispatch)
    sources: List[str] = dataclasses.field(default_factory=list)

    # -- reads ---------------------------------------------------------------
    def get(self, op: str, key: str, default: Any = None) -> Any:
        return self.entries.get(op, {}).get(key, default)

    def get_block(self, op: str, bucket: str, key: str,
                  default: Any = None) -> Any:
        return self.entries.get(op, {}).get("blocks", {}) \
            .get(bucket, {}).get(key, default)

    # -- writes --------------------------------------------------------------
    def set(self, op: str, key: str, value: Any) -> None:
        self.entries.setdefault(op, {})[key] = value

    def set_block(self, op: str, bucket: str, key: str, value: Any) -> None:
        self.entries.setdefault(op, {}).setdefault("blocks", {}) \
            .setdefault(bucket, {})[key] = value

    def merge(self, other: "TuningTable") -> None:
        """Overlay ``other`` onto this table (other wins; blocks deep-merge
        per bucket). Used default-then-cache and by sweep-tool fragments."""
        for op, entry in other.entries.items():
            mine = self.entries.setdefault(op, {})
            for key, val in entry.items():
                if key == "blocks":
                    blocks = mine.setdefault("blocks", {})
                    for bucket, cfg in val.items():
                        blocks.setdefault(bucket, {}).update(cfg)
                else:
                    mine[key] = val

    # -- serde ---------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {"schema": SCHEMA, "device_kind": self.device_kind,
                "entries": self.entries}

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "TuningTable":
        if not isinstance(d, dict) or d.get("schema") != SCHEMA:
            raise ValueError(
                f"not a {SCHEMA} document (schema={d.get('schema') if isinstance(d, dict) else type(d).__name__!r})")
        entries = d.get("entries")
        if not isinstance(entries, dict) or not all(
                isinstance(v, dict) for v in entries.values()):
            raise ValueError("tuning table 'entries' must map op -> dict")
        for op_name, entry in entries.items():
            # a schema-valid but malformed blocks value ("blocks": null, or
            # bucket -> scalar) must be rejected HERE so it lands in the
            # corrupt-table warn-once fallback instead of crashing merge()
            # inside every dispatch site's tuned() read
            if "blocks" in entry:
                blocks = entry["blocks"]
                if not isinstance(blocks, dict) or not all(
                        isinstance(cfg, dict) for cfg in blocks.values()):
                    raise ValueError(
                        f"tuning table entry '{op_name}': 'blocks' must "
                        f"map bucket -> dict")
        return TuningTable(device_kind=str(d.get("device_kind", "unknown")),
                           entries=entries)

    @staticmethod
    def load(path: str) -> "TuningTable":
        with open(path) as f:
            table = TuningTable.from_dict(json.load(f))
        table.source = path
        return table

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: a concurrent reader never sees half
        return path


# ---------------------------------------------------------------------------
# loading: checked-in tables; a measured overlay only on explicit request
# ---------------------------------------------------------------------------


def tuning_dir() -> str:
    """Where the autotuner and the sweep tools write measured tables."""
    return os.environ.get(ENV_DIR) or _CHECKOUT_TUNING_DIR


def cache_path(device_kind: Optional[str] = None) -> str:
    kind = device_kind or current_device_kind()
    return os.path.join(tuning_dir(), f"{kind}.json")


def default_table_paths(device_kind: str) -> List[str]:
    """Checked-in defaults: the generic table always, a per-kind table on
    top when one was committed after a device sweep."""
    paths = [os.path.join(_PACKAGE_TABLE_DIR, "default.json")]
    per_kind = os.path.join(_PACKAGE_TABLE_DIR, f"{device_kind}.json")
    if os.path.exists(per_kind):
        paths.append(per_kind)
    return paths


def _load_or_warn(table: TuningTable, path: str) -> None:
    if not os.path.exists(path):
        return
    try:
        table.merge(TuningTable.load(path))
        table.sources.append(path)
    except (ValueError, TypeError, AttributeError, OSError,
            json.JSONDecodeError) as e:
        # corrupt measured table: fall back to the checked-in defaults —
        # dispatch must stay deterministic, never crash. Warn once per path.
        if path not in _WARNED_PATHS:
            _WARNED_PATHS.add(path)
            logger.warning("ignoring corrupt tuning table %s: %s", path, e)


def active_table(device_kind: Optional[str] = None) -> TuningTable:
    """The table dispatch reads for a device kind, memoized: the
    checked-in tables, plus the measured overlay from
    ``DL4J_TPU_TUNING_DIR`` when (and only when) that variable is set —
    dispatch never depends on a file git does not see unless asked to."""
    kind = device_kind or current_device_kind()
    cached = _ACTIVE.get(kind)
    if cached is not None:
        return cached
    table = TuningTable(device_kind=kind)
    for path in default_table_paths(kind):
        _load_or_warn(table, path)
    if os.environ.get(ENV_DIR):
        _load_or_warn(table, cache_path(kind))
    _ACTIVE[kind] = table
    return table


def tuned(op: str, key: str, default: Any = None,
          bucket: Optional[str] = None) -> Any:
    """One measured value: the shape-bucket entry when present, else the
    op-level entry, else ``default``. This is THE read API every dispatch
    site uses; env overrides are applied by the caller (they must win)."""
    table = active_table()
    if bucket is not None:
        v = table.get_block(op, bucket, key)
        if v is not None:
            return v
    return table.get(op, key, default)


def tuned_block(op: str, key: str, size: int, bucket: str,
                fallback: Callable[[int], int]) -> int:
    """A measured block size, validated against the actual dimension — a
    tuned block that does not divide ``size`` falls back (tables describe
    buckets; a ragged real shape inside the bucket may not divide)."""
    v = tuned(op, key, None, bucket=bucket)
    if v:
        v = int(v)
        if size % v == 0:
            return v
    return fallback(size)


def on_reset(cb: Callable[[], None]) -> None:
    """Register a cache-invalidation hook (dispatch sites memoize derived
    values — e.g. ``flash_min_t`` — and must drop them with the tables)."""
    _RESET_CALLBACKS.append(cb)


def reset_tables() -> None:
    """Drop memoized tables (test seam; called after autotune() saves)."""
    _ACTIVE.clear()
    _WARNED_PATHS.clear()
    for cb in _RESET_CALLBACKS:
        cb()


# ---------------------------------------------------------------------------
# measurement: AOT-compiled timing that never touches the jit cache
# ---------------------------------------------------------------------------


def aot_time(fn: Callable, args: Sequence[Any], iters: int = 3,
             reps: int = 2) -> float:
    """Seconds per call, min over ``reps`` of ``iters`` calls each.

    The candidate is lowered and compiled AOT (``jit(fn).lower().compile()``
    — the SNIPPETS.md [1] idiom): the compiled executable is invoked
    directly, so candidate configurations never populate the process jit
    cache with entries real dispatch would then collide with."""
    import jax

    # graftshape: justified(GS001): AOT-timed candidate executables are deliberately cache-free and discarded after timing — ledgering them would record one first_compile per ladder rung as if it were serving traffic
    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    jax.block_until_ready(out)  # warm + fail loudly before timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _crossover(ladder: Sequence[int], pallas_ms: Dict[int, float],
               xla_ms: Dict[int, float]) -> int:
    """Smallest ladder point where the Pallas candidate wins; ladder points
    are scanned in order and the first win is sticky (the sweep shows wins
    are monotone in T past the crossover). If Pallas never wins —the CPU
    interpret-mode case — the threshold lands at 2× the largest measured
    point: pessimistic, deterministic, and re-measurable on a real chip."""
    for t in sorted(ladder):
        if pallas_ms[t] <= xla_ms[t]:
            return t
    return 2 * max(ladder)


@dataclasses.dataclass
class TuneReport:
    """What one autotune() pass measured (CLI/JSON surface)."""

    device_kind: str
    ops: List[str] = dataclasses.field(default_factory=list)
    measurements: int = 0
    seconds: float = 0.0
    table_path: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _span(op: str):
    from deeplearning4j_tpu import observe

    observe.metrics().counter("dl4j_tpu_tuning_runs_total", op=op).inc()
    return observe.tracer().span(f"tuning_{op}", category="tuning")


# -- per-op tuners -----------------------------------------------------------


def _tune_attention(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.pallas_attention import flash_attention
    from deeplearning4j_tpu.ops.registry import registry

    generic = registry().get("dot_product_attention").fn
    ladder = (32, 64) if smoke else (512, 1024, 2048, 4096, 8192)
    cands = ((8, 8), (16, 16)) if smoke else ((256, 256), (512, 512))
    bh, d = (2, 8) if smoke else (8, 64)
    r = np.random.RandomState(0)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("dot_product_attention"):
        for t in ladder:
            q = jnp.asarray(r.randn(bh, t, d).astype(np.float32))
            xla_ms[t] = aot_time(lambda q: generic(q, q, q), (q,))
            n += 1
            best = None
            for bq, bk in cands:
                sec = aot_time(
                    lambda q, _bq=bq, _bk=bk: flash_attention(
                        q, q, q, None, None, None, False, _bq, _bk, None,
                        0.0),
                    (q,))
                n += 1
                if best is None or sec < best[0]:
                    best = (sec, bq, bk)
            pallas_ms[t] = best[0]
            table.set_block("dot_product_attention", bucket_t(t),
                            "block_q", best[1])
            table.set_block("dot_product_attention", bucket_t(t),
                            "block_k", best[2])
        table.set("dot_product_attention", "flash_min_t",
                  _crossover(ladder, pallas_ms, xla_ms))
    return n


def _tune_fused_matmul(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.nn_ops import fused_matmul_bias_act
    from deeplearning4j_tpu.ops.pallas_matmul import \
        fused_matmul_bias_act_pallas

    shapes = ((16, 128, 128),) if smoke else \
        ((256, 512, 512), (512, 1024, 1024))
    cands = ((8, 128, 128), (16, 128, 128)) if smoke else \
        ((128, 256, 256), (256, 256, 512))
    r = np.random.RandomState(1)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("fused_matmul_bias_act"):
        for m, k, nn_ in shapes:
            x = jnp.asarray(r.randn(m, k).astype(np.float32))
            w = jnp.asarray((r.randn(k, nn_) * k ** -0.5).astype(np.float32))
            b = jnp.asarray(r.randn(nn_).astype(np.float32))
            xla_ms[m] = aot_time(
                lambda x, w, b: fused_matmul_bias_act.fn(
                    x, w, b, activation="gelu"), (x, w, b))
            n += 1
            best = None
            for bm, bk, bn in cands:
                if m % bm or k % bk or nn_ % bn:
                    continue
                sec = aot_time(
                    lambda x, w, b, _bm=bm, _bk=bk, _bn=bn:
                    fused_matmul_bias_act_pallas(
                        x, w, b, activation="gelu", block_m=_bm,
                        block_n=_bn, block_k=_bk),
                    (x, w, b))
                n += 1
                if best is None or sec < best[0]:
                    best = (sec, bm, bk, bn)
            if best is None:
                continue
            pallas_ms[m] = best[0]
            bucket = bucket_mkn(m, k, nn_)
            for key, val in (("block_m", best[1]), ("block_k", best[2]),
                             ("block_n", best[3])):
                table.set_block("fused_matmul_bias_act", bucket, key, val)
        if pallas_ms:
            table.set("fused_matmul_bias_act", "pallas_min_m",
                      _crossover(sorted(pallas_ms), pallas_ms, xla_ms))
    return n


def _tune_layernorm(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.pallas_layernorm import (
        fused_layer_norm, fused_layer_norm_pallas)

    shapes = ((16, 128),) if smoke else ((1024, 512), (8192, 1024))
    cands = (8, 16) if smoke else (64, 256)
    r = np.random.RandomState(2)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("fused_layer_norm"):
        for rows, d in shapes:
            x = jnp.asarray(r.randn(rows, d).astype(np.float32))
            g = jnp.asarray(r.rand(d).astype(np.float32) + 0.5)
            b = jnp.asarray(r.randn(d).astype(np.float32))
            xla_ms[rows] = aot_time(
                lambda x, g, b: fused_layer_norm.fn(x, g, b,
                                                    activation="gelu"),
                (x, g, b))
            n += 1
            best = None
            for br in cands:
                if rows % br:
                    continue
                sec = aot_time(
                    lambda x, g, b, _br=br: fused_layer_norm_pallas(
                        x, g, b, activation="gelu", block_rows=_br),
                    (x, g, b))
                n += 1
                if best is None or sec < best[0]:
                    best = (sec, br)
            if best is None:
                continue
            pallas_ms[rows] = best[0]
            table.set_block("fused_layer_norm", bucket_rows(rows),
                            "block_rows", best[1])
        if pallas_ms:
            table.set("fused_layer_norm", "min_rows",
                      _crossover(sorted(pallas_ms), pallas_ms, xla_ms))
    return n


def _tune_updater(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.pallas_updater import (
        fused_updater_step, fused_updater_helper)

    sizes = (1024,) if smoke else (1 << 16, 1 << 20)
    r = np.random.RandomState(3)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("fused_updater_step"):
        for size in sizes:
            p = jnp.asarray(r.randn(size).astype(np.float32))
            g = jnp.asarray(r.randn(size).astype(np.float32) * 0.01)
            z = jnp.zeros((size,), jnp.float32)
            lr = jnp.float32(1e-3)
            step = jnp.float32(0.0)
            args = (p, g, lr, step, z, z)
            xla_ms[size] = aot_time(
                lambda *a: fused_updater_step.fn(*a, kind="Adam"), args)
            sec = aot_time(
                lambda *a: fused_updater_helper(*a, kind="Adam"), args)
            n += 2
            pallas_ms[size] = sec
        table.set("fused_updater_step", "min_size",
                  _crossover(sizes, pallas_ms, xla_ms))
    return n


def _tune_int8(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.quantized import (
        matmul_int8, matmul_int8_pallas, quantize_int8)

    shapes = ((32, 128, 128),) if smoke else ((256, 512, 512),)
    cands = ((32, 128, 128),) if smoke else ((128, 256, 256), (256, 512, 256))
    r = np.random.RandomState(4)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("matmul_int8"):
        for m, k, nn_ in shapes:
            x = jnp.asarray(r.randn(m, k).astype(np.float32))
            wq, ws = quantize_int8.fn(
                jnp.asarray((r.randn(k, nn_) * k ** -0.5)
                            .astype(np.float32)), axis=0)
            xla_ms[m] = aot_time(
                lambda x, wq, ws: matmul_int8.fn(x, wq, ws), (x, wq, ws))
            n += 1
            best = None
            for bm, bk, bn in cands:
                if m % bm or k % bk or nn_ % bn:
                    continue
                sec = aot_time(
                    lambda x, wq, ws, _bm=bm, _bk=bk, _bn=bn:
                    matmul_int8_pallas(x, wq, ws, block_m=_bm, block_k=_bk,
                                       block_n=_bn),
                    (x, wq, ws))
                n += 1
                if best is None or sec < best[0]:
                    best = (sec, bm, bk, bn)
            if best is None:
                continue
            pallas_ms[m] = best[0]
            bucket = bucket_mkn(m, k, nn_)
            for key, val in (("block_m", best[1]), ("block_k", best[2]),
                             ("block_n", best[3])):
                table.set_block("matmul_int8", bucket, key, val)
        if pallas_ms:
            table.set("matmul_int8", "pallas_min_m",
                      _crossover(sorted(pallas_ms), pallas_ms, xla_ms))
    return n


def _tune_paged_decode(table: TuningTable, smoke: bool) -> int:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.pallas_attention import (
        _paged_decode_call, paged_decode_attention_xla)

    ladders = (2, 4) if smoke else (4, 16, 64)
    # heads * head_dim a multiple of 128: a width the kernel takes
    s_n, h, d, page = (2, 2, 64, 8) if smoke else (8, 8, 64, 16)
    r = np.random.RandomState(5)
    n = 0
    pallas_ms: Dict[int, float] = {}
    xla_ms: Dict[int, float] = {}
    with _span("paged_decode_attention"):
        for max_pages in ladders:
            n_pages = max_pages * s_n + 1
            q = jnp.asarray(r.randn(s_n, h, d).astype(np.float32))
            kv = jnp.asarray(
                r.randn(1, 2, n_pages, page, h * d).astype(np.float32))
            pt = jnp.asarray(
                r.randint(0, n_pages, (s_n, max_pages)).astype(np.int32))
            sl = jnp.asarray(
                np.full((s_n,), max_pages * page, np.int32))
            args = (q, kv, pt, sl)
            xla_ms[max_pages] = aot_time(paged_decode_attention_xla, args)
            pallas_ms[max_pages] = aot_time(_paged_decode_call, args)
            n += 2
        table.set("paged_decode_attention", "min_pages",
                  _crossover(ladders, pallas_ms, xla_ms))
    return n


_TUNERS: Tuple[Tuple[str, Callable[[TuningTable, bool], int]], ...] = (
    ("dot_product_attention", _tune_attention),
    ("fused_matmul_bias_act", _tune_fused_matmul),
    ("fused_layer_norm", _tune_layernorm),
    ("fused_updater_step", _tune_updater),
    ("matmul_int8", _tune_int8),
    ("paged_decode_attention", _tune_paged_decode),
)


def autotune(ops: Optional[Sequence[str]] = None, smoke: bool = False,
             save: bool = True,
             device_kind: Optional[str] = None) -> Tuple[TuningTable,
                                                         TuneReport]:
    """Measure candidate configurations and build a tuning table.

    ``smoke`` shrinks every ladder to shapes that finish in seconds on a
    CPU interpret-mode host (the ``make tune-smoke`` contract: exits 0
    anywhere, produces a valid table). ``save`` writes the table to
    :func:`tuning_dir` and invalidates the memoized readers; the
    measurement steers dispatch in the same process only when
    ``DL4J_TPU_TUNING_DIR`` names that directory (see
    :func:`active_table`)."""
    kind = device_kind or current_device_kind()
    table = TuningTable(device_kind=kind)
    report = TuneReport(device_kind=kind)
    t0 = time.perf_counter()
    wanted = set(ops) if ops else None
    for name, tuner in _TUNERS:
        if wanted is not None and name not in wanted:
            continue
        report.measurements += tuner(table, smoke)
        report.ops.append(name)
    report.seconds = round(time.perf_counter() - t0, 3)
    if save:
        # merge onto the existing cache table: an --ops subset re-tune must
        # refresh only what it measured, not discard every other op's
        # previously measured entries
        merged = TuningTable(device_kind=kind)
        _load_or_warn(merged, cache_path(kind))
        merged.merge(table)
        report.table_path = merged.save(cache_path(kind))
        reset_tables()
    return table, report
