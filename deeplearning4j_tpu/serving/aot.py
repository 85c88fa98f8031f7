"""Engine warm boot from the persistent AOT export cache.

The serving engine's compiled functions (``prefill`` / ``write_prompt`` /
``decode`` and, when configured, ``suffix_prefill`` / ``verify`` /
``copy_page`` plus the draft ``draft_prefill`` / ``draft_decode``) have
signatures that depend ONLY on server config — the bucketing discipline
PRs 7–18 enforce. That makes them perfect AOT-cache citizens: one export
per (engine fingerprint, device_kind, jax version) serves every process
with that config. :func:`warm_boot` pre-populates all of them BEFORE the
first request:

* **hit** — the entry deserializes into the live fn slot; the ledger
  records ``cache_hit``, the process pays zero fresh traces for it (the
  XLA backend compile of the deserialized StableHLO additionally hits
  jax's persistent compilation cache, armed by ExportCache).
* **miss** — the builder compiles as usual, the export is persisted, and
  the engine runs the SAME exported executable it just stored — so the
  populating (cold) leg and every warm restore are bit-identical by
  construction, not by luck.

Key-taking fns (``prefill``/``suffix_prefill``/``decode``) export as
raw-key computations (typed PRNG keys cannot cross ``jax.export``; see
autodiff/export.py) behind a thin wrapper that feeds
``jax.random.key_data(key)`` — the engine's dispatch sites are unchanged.

Activation: ``$DL4J_TPU_COMPILE_CACHE`` (:func:`maybe_warm_boot`, called
from ``GenerativeEngine.__init__`` and ``_recover``), or an explicit
:class:`~deeplearning4j_tpu.autodiff.export.ExportCache` passed to
:func:`warm_boot` (tests).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import export as jexport

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.autodiff.export import (
    ENV_DIR, ExportCache, _tree_spec_tokens, fingerprint_tokens,
    restore_callable, spec_of)


def engine_fingerprint(engine) -> str:
    """Identity of an engine's compiled-fn family: model config + param
    tree structure + the full serving geometry (prompt bucket, page
    geometry, prefix/speculative arms). Weight VALUES are excluded — the
    executables are functions of structure; params are arguments."""
    cache = engine.cache
    toks: List[Any] = [
        "serving", repr(engine.cfg), _tree_spec_tokens(engine.model.params),
        engine.max_prompt, engine.suffix_bucket,
        cache.page_size, cache.num_pages, cache.max_slots,
        cache.max_pages_per_seq, tuple(cache.kv.shape),
        str(cache.kv.dtype), engine.prefix is not None,
    ]
    if engine.spec is not None:
        spec = engine.spec
        toks += ["spec", repr(spec.draft.cfg),
                 _tree_spec_tokens(spec.draft.params), spec.k,
                 tuple(spec._kv_shape), str(spec._kv_dtype)]
    return fingerprint_tokens(*toks)


def _raw_key_adapter(inner, key_idx: int):
    """Export-side: take uint32 key data where ``inner`` takes a typed
    PRNG key (which cannot cross the export boundary)."""
    def raw(*args):
        args = list(args)
        args[key_idx] = jax.random.wrap_key_data(args[key_idx])
        return inner(*args)
    return raw


def _typed_key_adapter(call, key_idx: int):
    """Restore-side: the engine dispatches typed keys; the exported
    computation wants their raw data. Ledger markers mirror onto the
    wrapper — it is the object the dispatch sites register."""
    def fn(*args):
        args = list(args)
        args[key_idx] = jax.random.key_data(args[key_idx])
        return call(*args)

    fn._aot_restored = getattr(call, "_aot_restored", False)
    fn._obs_sigs = set(getattr(call, "_obs_sigs", ()))
    return fn


def _fn_table(engine) -> List[Dict[str, Any]]:
    """One descriptor per warm-bootable fn: cache key, live slot
    (owner object + attribute), builder, export arg specs, and the key
    arg index for raw-key adaptation (None for keyless fns). Specs
    mirror the dispatch sites' exact shapes/dtypes — config-stable by
    the bucketing contract."""
    cfg, cache = engine.cfg, engine.cache
    S, P = cache.page_table.shape
    SDS = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    kd = jax.random.key_data(jax.random.key(0))
    KD = SDS(tuple(kd.shape), kd.dtype)
    PARAMS = spec_of(engine.model.params)
    KV = SDS(tuple(cache.kv.shape), cache.kv.dtype)
    kv_prompt = SDS((cache.layers, cache.sides, engine.max_prompt,
                     cache.row_width),
                    cache.kv.dtype)
    table = [
        dict(key="prefill", owner=engine, attr="_prefill_fn",
             build=engine._build_prefill, key_idx=3, donate=(),
             specs=(PARAMS, SDS((1, engine.max_prompt), i32), SDS((), i32),
                    KD, SDS((1,), f32), SDS((1,), i32), SDS((1,), f32),
                    SDS((S,), i32), SDS((), i32))),
        dict(key="write_prompt", owner=engine, attr="_write_fn",
             build=engine._build_write, key_idx=None,
             specs=(KV, kv_prompt, SDS((P,), i32), SDS((), i32))),
        dict(key="decode", owner=engine, attr="_decode_fn",
             build=engine._build_decode, key_idx=6, donate=(1,),
             specs=(PARAMS, KV, SDS((S, P), i32), SDS((S,), i32),
                    SDS((S,), i32), SDS((S,), i32), KD, SDS((S,), f32),
                    SDS((S,), i32), SDS((S,), f32))),
    ]
    if engine.prefix is not None:
        table += [
            dict(key="suffix_prefill", owner=engine, attr="_suffix_fn",
                 build=engine._build_suffix, key_idx=6, donate=(1,),
                 specs=(PARAMS, KV, SDS((1, engine.suffix_bucket), i32),
                        SDS((), i32), SDS((), i32), SDS((P,), i32), KD,
                        SDS((1,), f32), SDS((1,), i32), SDS((1,), f32),
                        SDS((S,), i32), SDS((), i32))),
            dict(key="copy_page", owner=cache, attr="_copy_fn",
                 build=cache._build_copy, key_idx=None,
                 specs=(KV, SDS((), i32), SDS((), i32))),
        ]
    if engine.spec is not None:
        spec = engine.spec
        DPARAMS = spec_of(spec.draft.params)
        DKV = SDS(tuple(spec._kv_shape), spec._kv_dtype)
        table += [
            dict(key="verify", owner=engine, attr="_verify_fn",
                 build=engine._build_verify, key_idx=None,
                 specs=(PARAMS, KV, SDS((S, spec.k + 1), i32),
                        SDS((S,), i32), SDS((S, P), i32), SDS((S,), i32))),
            dict(key="draft_prefill", owner=spec, attr="_prefill_fn",
                 build=spec._build_prefill, key_idx=None,
                 specs=(DPARAMS, DKV, SDS((1, spec.max_prompt), i32),
                        SDS((), i32), SDS((), i32))),
            dict(key="draft_decode", owner=spec, attr="_propose_fn",
                 build=spec._build_propose, key_idx=None,
                 specs=(DPARAMS, DKV, SDS((S,), i32), SDS((S,), i32),
                        SDS((S,), i32))),
        ]
    return table


def warm_boot(engine, cache: Optional[ExportCache] = None) -> Dict[str, Any]:
    """Pre-populate every unbuilt compiled-fn slot from the AOT cache
    (hit) or by building+exporting+persisting (miss). Slots already
    holding a live fn are left alone — an in-process ``_recover`` keeps
    its compiled fns. Returns ``{"restored": [...], "exported": [...],
    "fingerprint": ...}``."""
    cache = cache or ExportCache.from_env()
    if cache is None:
        return {"restored": [], "exported": [], "fingerprint": None}
    fp = engine_fingerprint(engine)
    restored: List[str] = []
    exported_keys: List[str] = []
    for d in _fn_table(engine):
        if getattr(d["owner"], d["attr"]) is not None:
            continue
        exported = cache.load(fp, d["key"])
        if exported is not None:
            inner = restore_callable(exported, graph="serving",
                                     key=d["key"], hit=True)
            restored.append(d["key"])
        else:
            built = d["build"]()
            if d["key_idx"] is None:
                jitted = built
            else:
                jitted = jax.jit(_raw_key_adapter(built, d["key_idx"]),
                                 donate_argnums=d.get("donate", ()))
            t0 = time.perf_counter()
            exported = jexport.export(jitted)(*d["specs"])
            cache.observe_export_seconds(time.perf_counter() - t0)
            cache.store(fp, d["key"], exported, meta={"graph": "serving"})
            # run the freshly exported executable, not the in-process jit:
            # the populating leg and every warm restore share ONE artifact,
            # so bit-identity across legs holds by construction
            inner = restore_callable(exported, graph="serving",
                                     key=d["key"], hit=False)
            exported_keys.append(d["key"])
        fn = (inner if d["key_idx"] is None
              else _typed_key_adapter(inner, d["key_idx"]))
        setattr(d["owner"], d["attr"], fn)
    if restored or exported_keys:
        observe.log_event("aot_warm_boot", consumer="serving",
                          restored=restored, exported=exported_keys)
    return {"restored": restored, "exported": exported_keys,
            "fingerprint": fp}


def maybe_warm_boot(engine) -> Dict[str, Any]:
    """Env-gated :func:`warm_boot` — inert unless
    ``$DL4J_TPU_COMPILE_CACHE`` is set, so default construction (tests,
    unconfigured deployments) pays nothing; inert too for an engine whose
    cache is a state a slot (``models/served.py`` ``SlotState``) or whose
    model yields a block a step (``ServingPrograms.block``)."""
    if (not os.environ.get(ENV_DIR) or not hasattr(engine.cache, "page_table")
            or engine.block > 1):
        # the export table describes the one-token paged programs: an
        # engine over a pool of slot states, or of a block model, compiles
        # as it goes
        return {"restored": [], "exported": [], "fingerprint": None}
    return warm_boot(engine)
