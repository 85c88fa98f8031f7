"""The ``brumby`` family: the way into the program for Brumby-14B-Base as
one pipeline stage's chip serves it
(``GenerativeEngine(BrumbyModel(...)).start()`` / ``submit``), and the
comparison of what it served with the plain reference
(``reference/brumby.py``)."""

from __future__ import annotations

from typing import Any, Dict, List

from common import Checks
from families import gpt as served
from reference import brumby as ref

request_failed = served.request_failed


class ServeProgram(served.ServeProgram):
    """ONE started engine, as the ``gpt`` family's: only the model differs."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        # the program's modules FIRST: a tree without them fails here, in
        # seconds, before any weight is made
        from deeplearning4j_tpu.models.brumby import BrumbyConfig, BrumbyModel
        from deeplearning4j_tpu.serving import GenerativeEngine

        import jax.numpy as jnp

        self.cfg, self.mix = cfg, mix
        keys = [f.name for f in BrumbyConfig.__dataclass_fields__.values()
                if f.name in cfg]
        bcfg = BrumbyConfig(**dict({k: cfg[k] for k in keys},
                                   eos_token=mix.get("eos_token", -1)))
        weights = ref.make_weights(cfg, seed, jnp.dtype(cfg["param_dtype"]))
        self.model = BrumbyModel(bcfg, params=weights)
        eng = dict(mix["engine"])
        self.max_slots = eng["max_slots"]
        self.engine = GenerativeEngine(self.model, seed=seed & 0x7FFFFFFF,
                                       **eng).start()
        self.ask = dict(temperature=mix.get("temperature", 0.0),
                        eos_token=mix.get("eos_token", -1))


def verify(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
           sample: List[Dict[str, Any]], checks: Checks, *,
           limits=None, control=None) -> Dict[str, Any]:
    """The reference over each sampled prompt with its served tokens (prefill
    by the quadratic form and then decoding through the state pool, against
    the reference's quadratic form over the whole sequence); the widest gap
    by which a served token's logit lies below the reference's best is held
    to its limit. ``control``: judge, in the served tokens' place, the token
    that this stand-in (a lower precision, a planted fault) puts first at
    each position."""
    limits = limits or mix.get("limits") or cfg["limits"]
    got = ref.served_gaps(cfg, seed, sample, control=control,
                          max_new=int(mix["new_tokens"]["max"]),
                          max_total=int(mix["max_total"]))
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
    """What has to come out as not correct: the precisions below the
    configuration's (the state in bfloat16 first), and a fault planted in
    each part of the retention layer."""
    for control in ref.CONTROLS:
        yield "control_" + control, ctx["sample"], {"control": control}
    for fault in ref.FAULTS:
        yield "fault_" + fault, ctx["sample"], {"control": fault}
