"""The ``gpt`` family: the way into the program for a decoder that is served
(``GenerativeEngine.start()`` / ``submit``), and the comparison of what it
served with the plain reference (``reference/gpt.py``)."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from common import Checks
from reference import gpt as ref

NORMAL_FINISH = ("eos", "length")


class ServeProgram:
    """The system under test: ONE started engine, warmed by the set-up and
    then driven by the window."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
        from deeplearning4j_tpu.serving import GenerativeEngine

        self.cfg, self.mix = cfg, mix
        if cfg.get("matmul_precision"):
            # the configuration states float32: the program's own switch for
            # float32-class multiplies on the MXU (GptModel, unlike the
            # program's other models, does not take it from its dtype)
            from deeplearning4j_tpu.environment import environment

            env = environment()
            env.matmul_precision = cfg["matmul_precision"]
            env.apply_jax_config()
        gcfg = GptConfig(
            vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
            layers=cfg["n_layer"], heads=cfg["n_head"],
            intermediate=cfg.get("n_inner") or 4 * cfg["n_embd"],
            max_position=cfg["n_positions"],
            layer_norm_eps=cfg["layer_norm_epsilon"])
        weights = ref.make_weights(ref.tree_spec(cfg), seed,
                                   jnp.dtype(cfg["param_dtype"]))
        self.model = GptModel(gcfg, params=weights)
        eng = dict(mix["engine"])
        self.max_slots = eng["max_slots"]
        self.engine = GenerativeEngine(self.model, seed=seed & 0x7FFFFFFF,
                                       **eng).start()
        self.ask = dict(temperature=mix.get("temperature", 0.0),
                        eos_token=mix.get("eos_token", -1))

    def submit(self, request: Dict[str, Any]):
        return self.engine.submit(
            request["prompt"], max_new_tokens=request["max_new_tokens"],
            **self.ask)

    def spans(self) -> List[Dict[str, Any]]:
        """The program's spans: name, perf_counter start, seconds, args."""
        import time

        from deeplearning4j_tpu import observe

        tr = observe.tracer()
        tr.instant("bench_sync")
        now = time.perf_counter()
        events = list(tr.to_dict()["traceEvents"])
        sync = events[-1]["ts"]
        return [{"name": ev["name"], "start": now + (ev["ts"] - sync) / 1e6,
                 "seconds": ev["dur"] / 1e6, "args": ev.get("args", {})}
                for ev in events if ev.get("ph") == "X"]

    def clear_spans(self) -> None:
        from deeplearning4j_tpu import observe

        observe.tracer().clear()

    def dispatch_counts(self) -> Dict[str, int]:
        from deeplearning4j_tpu import observe

        return observe.dispatch_summary()

    def stop(self) -> bool:
        self.engine.stop()
        return bool(self.engine.stopped_cleanly)

    def free(self) -> None:
        self.engine.cache.kv = None
        self.engine = None
        self.model = None


def request_failed(request: Dict[str, Any], result) -> bool:
    return (result is None or result.finish_reason not in NORMAL_FINISH
            or len(result.tokens) != request["max_new_tokens"])


def verify(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
           sample: List[Dict[str, Any]], checks: Checks, *,
           limits=None, control=None) -> Dict[str, Any]:
    """The reference over each sampled prompt with its served tokens; the
    widest gap by which a served token's logit lies below the reference's
    best is held to its limit. ``control``: judge, in the served tokens'
    place, the token that this lower precision puts first at each position
    of the same prompts and tokens."""
    limits = limits or mix.get("limits") or cfg["limits"]
    got = ref.served_gaps(cfg, seed, sample, control=control,
                          max_new=int(mix["new_tokens"]["max"]))
    gap = got["control_logit_gap" if control else "served_logit_gap"]
    checks.add("served_logit_gap", gap, limits["served_logit_gap"])
    checks.require("served_tokens_read", got["tokens_read"] > 0,
                   f"{got['tokens_read']} served tokens read, "
                   f"{got['distinct_tokens']} distinct; the reference's "
                   f"margin between its two best: least "
                   f"{got['top2_margin_min'] or 0:.3g}, median "
                   f"{got['top2_margin_median'] or 0:.3g}")
    return got


def stand_ins(cfg, mix, seed, ctx):
    """What has to come out as not correct: the controls, as ``verify``
    judges them. Yields (tag, readings, verify's further arguments)."""
    for control in ("bfloat16", "int8"):
        yield "control_" + control, ctx["sample"], {"control": control}
