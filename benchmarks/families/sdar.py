"""The ``sdar`` family: the way into the program for SDAR-30B-A3B-Chat as
one pipeline stage's chip serves it
(``GenerativeEngine(SdarModel(...)).start()`` / ``submit``), and the
comparison of what it served with the plain reference (``reference/sdar.py``),
which replays the block diffusion schedule from the served tokens and the
pass at which each was unmasked."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from common import Checks
from families import gpt as served
from reference import sdar as ref

request_failed = served.request_failed

# (prompt, tokens) as the caller saw them -> the pass of its block at which
# each token was unmasked: what the comparison replays. The loop's sample
# keeps prompts and tokens alone, so the program's results leave them here.
_PASSES: Dict[tuple, np.ndarray] = {}


def _key(prompt, tokens) -> tuple:
    return (np.asarray(prompt, np.int32).tobytes(),
            np.asarray(tokens, np.int32).tobytes())


def clean_prompt(cfg: Dict[str, Any], prompt) -> np.ndarray:
    """The traffic draws ids over the whole vocabulary; the mask token may
    not stand in a prompt (the engine refuses it), so an id equal to it is
    read as the next id, here and in the comparison alike."""
    m_id = ref.mask_of(cfg)
    prompt = np.asarray(prompt, np.int32)
    return np.where(prompt == m_id, (m_id + 1) % cfg["vocab_size"], prompt)


class ServeProgram(served.ServeProgram):
    """ONE started engine, as the ``gpt`` family's: only the model and the
    prompts' mask ids differ, and each result's unmask passes are kept."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        # the program's modules FIRST: a tree without them fails here, in
        # seconds, before any weight is made
        from deeplearning4j_tpu.models.sdar import SdarConfig, SdarModel
        from deeplearning4j_tpu.serving import GenerativeEngine

        import jax.numpy as jnp

        self.cfg, self.mix = cfg, mix
        keys = [f.name for f in SdarConfig.__dataclass_fields__.values()
                if f.name in cfg]
        scfg = SdarConfig(**dict({k: cfg[k] for k in keys},
                                 eos_token=mix.get("eos_token", -1)))
        weights = ref.make_weights(cfg, seed, jnp.dtype(cfg["param_dtype"]))
        self.model = SdarModel(scfg, params=weights)
        eng = dict(mix["engine"])
        self.max_slots = eng["max_slots"]
        self.engine = GenerativeEngine(self.model, seed=seed & 0x7FFFFFFF,
                                       **eng).start()
        self.ask = dict(temperature=mix.get("temperature", 0.0),
                        eos_token=mix.get("eos_token", -1))

    def submit(self, request: Dict[str, Any]):
        prompt = request["prompt"]
        fut = self.engine.submit(clean_prompt(self.cfg, prompt),
                                 max_new_tokens=request["max_new_tokens"],
                                 **self.ask)

        def keep(f):
            if f.exception() is None and f.result().unmask_pass is not None:
                res = f.result()
                _PASSES[_key(prompt, res.tokens)] = res.unmask_pass

        fut.add_done_callback(keep)
        return fut


def with_passes(cfg, sample: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The sampled requests as the reference replays them: the prompt as
    served and each token's unmask pass."""
    return [{"prompt": clean_prompt(cfg, r["prompt"]), "tokens": r["tokens"],
             "unmask_pass": _PASSES[_key(r["prompt"], r["tokens"])]}
            for r in sample if len(r["tokens"])]


def verify(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
           sample: List[Dict[str, Any]], checks: Checks, *,
           limits=None, control=None) -> Dict[str, Any]:
    """The reference replays each sampled request's passes (the prompt's
    whole blocks prefilled, then every denoising pass of every block with
    the block as the program had it, against the clean blocks before it);
    at each pass the widest gap by which the served token's logit lies
    below the reference's best, and by which the reference's confidence at
    the unmasked position lies below its best over the masked ones, are
    held to their limits. ``control``: judge, in the program's place, the
    pick of this stand-in (a lower precision, a planted fault) at each
    pass."""
    limits = limits or mix.get("limits") or cfg["limits"]
    got = ref.served_gaps(cfg, seed, with_passes(cfg, sample),
                          control=control)
    pre = "control_" if control else "served_"
    checks.add("served_logit_gap", got[pre + "logit_gap"],
               limits["served_logit_gap"])
    checks.add("served_order_gap", got[pre + "order_gap"],
               limits["served_order_gap"])
    checks.require("served_passes_read", got["passes_read"] > 0,
                   f"{got['passes_read']} passes read, "
                   f"{got['distinct_tokens']} distinct tokens; the "
                   f"reference's margin between its two most confident "
                   f"masked positions: least "
                   f"{got['order_margin_min'] or 0:.3g}, median "
                   f"{got['order_margin_median'] or 0:.3g}")
    return got


def stand_ins(cfg, mix, seed, ctx):
    """What has to come out as not correct: the precisions below the
    configuration's, and a fault planted in each part of the block
    mechanism and of the layer."""
    for control in ref.CONTROLS:
        yield "control_" + control, ctx["sample"], {"control": control}
    for fault in ref.FAULTS:
        yield "fault_" + fault, ctx["sample"], {"control": fault}
