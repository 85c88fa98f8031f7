"""AOT export + persistent executable cache (ROADMAP item: compile once,
serve every shape, restart warm).

Compilation is the tax every cold process pays: both supervisors (the
serving engine's ``_recover`` in a fresh process, ``TrainingSupervisor.
resume``) re-jit every compiled fn from scratch. This module makes compiled
computations a *persistent artifact* instead:

* **Export** — an optimized computation (a SameDiff :class:`GraphPlan`
  exec fn, an MLN fused train step, or a serving engine fn) is exported
  through ``jax.export`` into a serialized StableHLO module. Batch axes
  are exported **symbolically** (``jax.export.symbolic_shape``) so ONE
  serialized executable serves arbitrary batch sizes — a fresh signature
  on a restored fn is served without a retrace, and the recompile ledger
  records it as ``cache_hit`` rather than ``new_shape``.
* **Cache** — :class:`ExportCache` persists serialized exports under
  ``$DL4J_TPU_COMPILE_CACHE`` following ops/tuning.py's table-cache
  discipline: atomic tmp+``os.replace`` writes, corrupt-entry warn-once
  fallback to a fresh compile, entries keyed on
  ``(fingerprint, device_kind, jax version)`` so a jax upgrade or a
  different accelerator invalidates by construction.
* **Restore** — :func:`restore_callable` deserializes an entry back into
  a callable and registers it on the recompile ledger with the
  ``cache_hit`` cause (warm restores are attributable, not invisible).

Typed PRNG keys (jax's ``key<fry>`` dtype) cannot cross the export
boundary, so key-taking fns are exported as *raw-key* wrappers taking the
``uint32`` key data and rebuilding the typed key with
``jax.random.wrap_key_data`` inside the computation; the restore-side
wrapper feeds ``jax.random.key_data(key)``. Outputs are bit-identical to
the in-process jit (test-asserted in tests/test_export.py).

Consumers: ``serving/aot.py`` (engine warm boot — the six config-stable
fns plus the draft fns), :func:`warm_boot_net` (``TrainingSupervisor.
resume``), and :func:`install_exec` (SameDiff whole-graph exec).
"""

from __future__ import annotations

import base64
import hashlib
import json
import logging
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jexport

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.environment import (
    disable_compile_cache, enable_compile_cache)
from deeplearning4j_tpu.ops.tuning import current_device_kind

logger = logging.getLogger(__name__)

SCHEMA = "dl4j_tpu_aot_v1"
ENV_DIR = "DL4J_TPU_COMPILE_CACHE"

# warn-once set for corrupt/stale entries (tuning-table discipline): the
# first bad load of a path logs a warning, later loads stay silent misses
_WARNED_PATHS: set = set()


def reset_export_cache() -> None:
    """Test seam: forget warn-once state (mirrors tuning.reset_tables)
    and turn JAX's persistent cache back off, so later compiles in the
    same process stop paying cache serialization."""
    _WARNED_PATHS.clear()
    disable_compile_cache()


class ExportCache:
    """Persistent on-disk cache of serialized ``jax.export`` artifacts.

    One JSON document per entry at ``<root>/<device_kind>/<digest>.json``:
    ``{schema, key, fingerprint, jax_version, device_kind, created, meta,
    payload}`` with the serialized Exported base64-encoded in ``payload``.
    The digest already hashes (fingerprint, device_kind, jax version); the
    stored fields are re-checked at load so a hand-copied or stale file
    can never restore under the wrong toolchain — it degrades to a miss.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(os.path.expanduser(root))
        self.device_kind = current_device_kind()
        m = observe.metrics()
        self._hits = m.counter("dl4j_tpu_aot_cache_hits")
        self._misses = m.counter("dl4j_tpu_aot_cache_misses")
        self._export_h = m.histogram("dl4j_tpu_aot_export_seconds")
        # the export payload caches the *program*; JAX's own persistent
        # cache (placed by the one shared rule, not under ``root``) caches
        # the *backend binary*, so a warm restore skips that compile too
        enable_compile_cache()

    @classmethod
    def from_env(cls) -> Optional["ExportCache"]:
        """The cache configured by ``$DL4J_TPU_COMPILE_CACHE``, or None —
        the whole AOT layer is inert unless the env var opts in."""
        root = os.environ.get(ENV_DIR)
        return cls(root) if root else None

    # ------------------------------------------------------------------ keys
    def digest(self, fingerprint: str, key: str) -> str:
        raw = "|".join((SCHEMA, fingerprint, key, self.device_kind,
                        jax.__version__))
        return hashlib.sha256(raw.encode()).hexdigest()

    def _path(self, digest: str) -> str:
        return os.path.join(self.root, self.device_kind, digest + ".json")

    # ------------------------------------------------------------------- i/o
    def store(self, fingerprint: str, key: str, exported,
              meta: Optional[Dict[str, Any]] = None) -> str:
        """Atomically persist one exported fn. Returns the entry path."""
        digest = self.digest(fingerprint, key)
        path = self._path(digest)
        doc = {
            "schema": SCHEMA,
            "key": key,
            "fingerprint": fingerprint,
            "jax_version": jax.__version__,
            "device_kind": self.device_kind,
            "created": time.time(),
            "meta": dict(meta or {}),
            "payload": base64.b64encode(exported.serialize()).decode("ascii"),
        }
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)  # atomic: readers never see a torn entry
        return path

    def _load_doc(self, path: str) -> Optional[Dict[str, Any]]:
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            if doc.get("schema") != SCHEMA:
                raise ValueError(f"schema {doc.get('schema')!r} != {SCHEMA}")
            return doc
        except FileNotFoundError:
            return None
        except (ValueError, TypeError, KeyError, OSError,
                json.JSONDecodeError) as e:
            if path not in _WARNED_PATHS:
                _WARNED_PATHS.add(path)
                logger.warning(
                    "ignoring corrupt AOT cache entry %s (%r) — "
                    "falling back to fresh compile", path, e)
            return None

    def load(self, fingerprint: str, key: str):
        """Deserialized ``Exported`` for (fingerprint, key), or None on
        miss/corrupt/stale — every non-hit degrades to a fresh compile."""
        path = self._path(self.digest(fingerprint, key))
        doc = self._load_doc(path)
        if doc is not None and (doc.get("jax_version") != jax.__version__
                                or doc.get("device_kind") != self.device_kind):
            # belt-and-braces: the digest already pins both, but a renamed
            # or hand-copied file must still never restore cross-toolchain
            if path not in _WARNED_PATHS:
                _WARNED_PATHS.add(path)
                logger.warning(
                    "ignoring stale AOT cache entry %s "
                    "(jax %s/%s, device %s/%s)", path,
                    doc.get("jax_version"), jax.__version__,
                    doc.get("device_kind"), self.device_kind)
            doc = None
        if doc is None:
            self._misses.inc()
            return None
        try:
            exported = jexport.deserialize(
                base64.b64decode(doc["payload"]))
        except Exception as e:
            if path not in _WARNED_PATHS:
                _WARNED_PATHS.add(path)
                logger.warning(
                    "ignoring undeserializable AOT cache entry %s (%r) — "
                    "falling back to fresh compile", path, e)
            self._misses.inc()
            return None
        self._hits.inc()
        return exported

    def entries(self) -> Iterator[Dict[str, Any]]:
        """Metadata of every readable entry for this device kind (payload
        omitted) — the scan warm_boot_net uses to find a net's steps."""
        d = os.path.join(self.root, self.device_kind)
        try:
            names = sorted(os.listdir(d))
        except OSError:
            return
        for name in names:
            if not name.endswith(".json"):
                continue
            doc = self._load_doc(os.path.join(d, name))
            if doc is not None and doc.get("jax_version") == jax.__version__:
                yield {k: doc[k] for k in
                       ("key", "fingerprint", "meta", "created")}

    def observe_export_seconds(self, seconds: float) -> None:
        """Feed the ``dl4j_tpu_aot_export_seconds`` histogram. Export
        sites call ``jax.export.export`` inline (graftshape's GS001 sees
        the jit→export flow in-module) and time around it."""
        self._export_h.observe(seconds)


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------


def restore_callable(exported, *, graph: str, key: str, hit: bool,
                     polymorphic: bool = False,
                     signature: Optional[str] = None):
    """Wrap a (de)serialized ``Exported`` back into a jitted callable and
    mark it for the recompile ledger.

    ``hit=True`` (restored from a populated cache) registers a restore
    event immediately — ``cache_hit``, attributed here — and marks the fn
    so every later dispatch-site registration also records ``cache_hit``:
    the warm leg's ledger shows zero fresh compiles for restored fns.
    ``hit=False`` (the exporting process itself installs the executable it
    just built, keeping both legs on the SAME compiled artifact for
    bit-identity) leaves the first dispatch to record ``first_compile`` as
    usual. ``polymorphic=True`` marks a symbolic-batch-dim export: later
    *new* signatures are served by the same executable, so they record
    ``cache_hit`` instead of ``new_shape``."""
    fn = jax.jit(exported.call)
    fn._aot_restored = bool(hit)
    if polymorphic:
        fn._aot_polymorphic = True
    if hit:
        observe.note_jit_signature(
            fn, graph=graph, key=key,
            signature=signature or f"aot[{key}]")
    return fn


def spec_of(tree, symbolic_axis0=None):
    """ShapeDtypeStruct pytree mirroring ``tree``; with ``symbolic_axis0``
    (a dim name, or an already-built symbolic dim when several argument
    trees must share ONE symbolic scope) every leaf's leading axis
    becomes that symbolic dim (batch)."""
    dim = symbolic_axis0
    if isinstance(dim, str):
        dim = jexport.symbolic_shape(dim)[0]

    def one(a):
        if a is None:
            return None
        a = jnp.asarray(a)
        shape = tuple(a.shape)
        if dim is not None and shape:
            shape = (dim,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


def fingerprint_tokens(*tokens) -> str:
    """sha256 over a flat token tuple — config-identity fingerprints for
    consumers without a GraphPlan (engine configs, net configs)."""
    h = hashlib.sha256()
    for t in tokens:
        h.update(repr(t).encode())
        h.update(b"|")
    return h.hexdigest()


def _tree_spec_tokens(tree) -> List[Tuple[str, Tuple[int, ...], str]]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(path), tuple(np.shape(a)),
             np.dtype(getattr(a, "dtype", np.asarray(a).dtype)).name)
            for path, a in leaves]


def net_fingerprint(net) -> str:
    """Identity of an MLN-style net for cache keying: the layer config
    plus the full param/opt/net-state tree structure (shapes + dtypes).
    Weight VALUES are deliberately excluded — the executable is a
    function of structure, and params are runtime arguments."""
    conf = getattr(net, "conf", None)
    try:
        conf_token = conf.to_json()
    except AttributeError:
        conf_token = repr(conf)
    return fingerprint_tokens(
        "mln", conf_token,
        _tree_spec_tokens(net.params),
        _tree_spec_tokens(net.opt_state),
        _tree_spec_tokens(net.net_state))


# ---------------------------------------------------------------------------
# MLN train step (TrainingSupervisor.resume's restore consumer)
# ---------------------------------------------------------------------------


def _mln_raw_step(inner):
    """Raw-key adapter around a jitted train step: typed PRNG keys cannot
    cross the export boundary, so the exported computation takes uint32
    key data and rebuilds the key inside."""
    def raw_step(params, opt_state, net_state, step, key_data,
                 features, labels, fmask, lmask):
        return inner(params, opt_state, net_state, step,
                     jax.random.wrap_key_data(key_data),
                     features, labels, fmask, lmask)
    return raw_step


def _mln_wrapper(net, restored):
    """fit()-compatible step fn over a restored symbolic-batch executable.

    Converts the typed key to raw key data per call. The export covered
    the dominant signature (mask-free, fixed trailing dims, symbolic
    batch); a batch outside it — masks present, or different feature
    dims — permanently falls back to a freshly built plain jit, clearing
    the ledger markers so later events report honestly."""
    state: Dict[str, Any] = {"plain": None}

    def step(params, opt_state, net_state, step_i, key, x, y, fm, lm):
        if state["plain"] is None and fm is None and lm is None:
            try:
                return restored(params, opt_state, net_state, step_i,
                                jax.random.key_data(key), x, y, fm, lm)
            except (TypeError, ValueError):
                pass  # aval/structure mismatch — fall back below
        if state["plain"] is None:
            state["plain"] = net._make_train_step()
            step._aot_restored = False
            step._aot_polymorphic = False
        return state["plain"](params, opt_state, net_state, step_i, key,
                              x, y, fm, lm)

    return step


def export_train_step(net, features, labels,
                      cache: Optional[ExportCache] = None,
                      batch_symbol: str = "b") -> Optional[str]:
    """Export ``net``'s fused train step with a symbolic batch dim and
    persist it; install the SAME exported executable as the net's live
    step fn so this (populating) process and every warm restore run one
    artifact — bit-identity across legs by construction.

    Returns the cache entry path, or None when no cache is configured."""
    cache = cache or ExportCache.from_env()
    if cache is None:
        return None
    inner = net._make_train_step()
    jitted = jax.jit(_mln_raw_step(inner), donate_argnums=(0, 1, 2))
    kd = jax.random.key_data(net._key)
    b = jexport.symbolic_shape(batch_symbol)[0]  # ONE scope for x AND y
    specs = (
        spec_of(net.params), spec_of(net.opt_state), spec_of(net.net_state),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct(tuple(kd.shape), kd.dtype),
        spec_of(jnp.asarray(features), b),
        spec_of(jnp.asarray(labels), b),
        None, None,
    )
    fp = net_fingerprint(net)
    t0 = time.perf_counter()
    exported = jexport.export(jitted)(*specs)
    cache.observe_export_seconds(time.perf_counter() - t0)
    path = cache.store(fp, "train_step", exported, meta={
        "graph": "mln",
        "feature_dims": list(np.shape(features)[1:]),
        "label_dims": list(np.shape(labels)[1:]),
    })
    restored = restore_callable(exported, graph="mln", key="train_step",
                                hit=False, polymorphic=True)
    wrapper = _mln_wrapper(net, restored)
    wrapper._aot_restored = False
    wrapper._aot_polymorphic = True
    net._jit_cache["train_step"] = wrapper
    return path


def warm_boot_net(net, cache: Optional[ExportCache] = None) -> int:
    """Restore every cached step for this net's fingerprint into its
    ``_jit_cache`` — the training half of cold-start restore, called by
    ``TrainingSupervisor.resume`` in a fresh process. Zero fresh XLA
    compiles for restored steps: the first fit batch dispatches straight
    into the deserialized executable and the ledger records only
    ``cache_hit``. Returns the number of steps restored (0 when no cache
    is configured or nothing matches)."""
    cache = cache or ExportCache.from_env()
    if cache is None or not hasattr(net, "_jit_cache"):
        return 0
    fp = net_fingerprint(net)
    restored = 0
    for entry in cache.entries():
        if entry["fingerprint"] != fp or entry["key"] in net._jit_cache:
            continue
        exported = cache.load(fp, entry["key"])
        if exported is None:
            continue
        fn = restore_callable(exported, graph=entry["meta"].get("graph", "mln"),
                              key=entry["key"], hit=True, polymorphic=True)
        wrapper = _mln_wrapper(net, fn)
        wrapper._aot_restored = True
        wrapper._aot_polymorphic = True
        # the restore event was recorded on the inner fn; mirror the seen
        # set onto the wrapper fit() registers against
        wrapper._obs_sigs = set(fn._obs_sigs)
        net._jit_cache[entry["key"]] = wrapper
        restored += 1
    if restored:
        observe.log_event("aot_warm_boot", consumer="mln", restored=restored)
    return restored


def maybe_warm_boot_net(net) -> int:
    """Env-gated :func:`warm_boot_net` — inert without the cache dir."""
    if not os.environ.get(ENV_DIR):
        return 0
    return warm_boot_net(net)


# ---------------------------------------------------------------------------
# SameDiff whole-graph exec
# ---------------------------------------------------------------------------


def samediff_fingerprint(sd, outputs: Tuple[str, ...]) -> str:
    """Plan-identity fingerprint for a SameDiff output set: the optimized
    GraphPlan hash when the optimizer is on (autodiff/optimize.py
    ``GraphPlan.fingerprint``), else the raw recording's node/var
    structure; either way joined with the VARIABLE argument specs."""
    plan = sd._graph_plan(tuple(outputs))
    if plan is not None:
        plan_token = plan.fingerprint()
    else:
        plan_token = fingerprint_tokens(
            [(n.op, tuple(n.inputs),
              sorted((k, repr(v)) for k, v in n.kwargs.items()),
              tuple(n.outputs)) for n in sd._needed_nodes(tuple(outputs))],
            tuple(outputs))
    var_specs = sorted(
        (n, tuple(np.shape(a)), np.dtype(a.dtype).name)
        for n, a in sd._arrays.items()
        if sd._vars[n].vtype != "CONSTANT")
    return fingerprint_tokens("samediff", plan_token, var_specs)


def export_exec(sd, feeds: Dict[str, Any], outputs,
                cache: Optional[ExportCache] = None,
                batch_symbol: Optional[str] = "b") -> Optional[str]:
    """Export a SameDiff output set's whole-graph exec fn (symbolic batch
    over the feeds) and persist it; install the exported executable as the
    live exec fn (see :func:`export_train_step` for the bit-identity
    rationale). Returns the entry path, or None without a cache."""
    if isinstance(outputs, str):
        outputs = [outputs]
    outputs = tuple(outputs)
    cache = cache or ExportCache.from_env()
    if cache is None:
        return None
    fn = sd._exec_fn(outputs)  # CompiledGraph (builds + caches the plan)
    var_arrays = sd._var_arrays(fn)
    feed_arrays = {k: jnp.asarray(v) for k, v in feeds.items()}
    specs = (spec_of(var_arrays),
             spec_of(feed_arrays, batch_symbol))
    fp = samediff_fingerprint(sd, outputs)
    t0 = time.perf_counter()
    exported = fn.export(*specs)  # CompiledGraph.export — jexport in-module
    cache.observe_export_seconds(time.perf_counter() - t0)
    path = cache.store(fp, "exec", exported, meta={
        "graph": "samediff", "outputs": list(outputs)})
    install_exec(sd, exported, outputs, fn_const_names=fn._const_names,
                 hit=False)
    return path


class _RestoredGraph:
    """Stands where ``CompiledGraph`` would in ``SameDiff._jit_cache``:
    callable on (var_arrays, feeds), carries ``_const_names`` (the
    VARIABLE/feed split) and ``stats`` (None — the restore never re-ran
    the optimizer, so there are no fresh timings to report)."""

    def __init__(self, call, const_names):
        self._call = call
        self._const_names = frozenset(const_names)
        self.stats = None
        self._aot_restored = getattr(call, "_aot_restored", False)
        self._aot_polymorphic = getattr(call, "_aot_polymorphic", False)
        self._obs_sigs = set(getattr(call, "_obs_sigs", ()))

    def __call__(self, var_arrays, feeds):
        return self._call(var_arrays, feeds)


def install_exec(sd, exported, outputs, *, fn_const_names=None,
                 hit: bool = True):
    """Install a (de)serialized exec executable into ``sd._jit_cache`` so
    ``output()`` dispatches straight into it. Returns the shim."""
    outputs = tuple([outputs] if isinstance(outputs, str) else outputs)
    if fn_const_names is None:
        plan = sd._graph_plan(outputs)
        const_names = set(sd._const_env())
        if plan is not None:
            const_names |= set(plan.extra_consts)
    else:
        const_names = set(fn_const_names)
    fn = restore_callable(exported, graph="samediff", key="exec", hit=hit,
                          polymorphic=True)
    shim = _RestoredGraph(fn, const_names)
    cache_key = ("exec", outputs, bool(sd.optimize), sd._effective_passes())
    sd._jit_cache[cache_key] = shim
    return shim


def warm_boot_samediff(sd, outputs,
                       cache: Optional[ExportCache] = None) -> bool:
    """Restore a cached exec for ``(sd, outputs)`` if present. Returns
    True on restore — the next ``output()`` call runs the deserialized
    executable with only ``cache_hit`` ledger events."""
    outputs = tuple([outputs] if isinstance(outputs, str) else outputs)
    cache = cache or ExportCache.from_env()
    if cache is None:
        return False
    fp = samediff_fingerprint(sd, outputs)
    exported = cache.load(fp, "exec")
    if exported is None:
        return False
    install_exec(sd, exported, outputs, hit=True)
    observe.log_event("aot_warm_boot", consumer="samediff",
                      outputs=list(outputs))
    return True
