"""The ``bert`` family: the way into the program for a BERT configuration
(``BertModel.fit_mlm_scanned``), the readings the comparison needs, and the
comparison with the plain reference (``reference/bert.py``)."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from common import Checks
from reference import bert as ref


class TrainProgram:
    """The system under test: ONE BertModel, whose compiled step the set-up
    drives through its first steps and the window then keeps driving."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.bert import BertConfig, BertModel
        from deeplearning4j_tpu.nn.updater import Adam

        self.cfg, self.mix = cfg, mix
        opt = cfg["optimizer"]
        assert opt["kind"] == "Adam", opt
        dtype = jnp.dtype(cfg["param_dtype"])
        bert_cfg = BertConfig(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            intermediate=cfg["intermediate_size"],
            max_position=cfg["max_position_embeddings"],
            type_vocab=cfg["type_vocab_size"],
            dropout=cfg["hidden_dropout_prob"],
            layer_norm_eps=cfg["layer_norm_eps"])
        assert cfg["hidden_dropout_prob"] == cfg["attention_probs_dropout_prob"]
        self.model = BertModel(
            bert_cfg, seed=ref.model_seed(seed), dtype=dtype,
            updater=Adam(learning_rate=opt["learning_rate"],
                         beta1=opt["beta1"], beta2=opt["beta2"],
                         epsilon=opt["epsilon"]))
        # the benchmark's weights in the program's place (the reference
        # makes the same from the seed and takes nothing from the program)
        spec = ref.tree_spec(cfg)
        self.model.params = ref.make_weights(spec, seed, dtype)
        self.model.opt_state = jax.tree.map(self.model.updater.init_state,
                                            self.model.params)
        self._p0 = ref.make_weights(spec, seed, dtype)
        self.feed = {k: jnp.asarray(v)
                     for k, v in ref.make_feed(cfg, mix, seed).items()}
        # ONE step to a call: the comparison reads Adam's state after the
        # first step and the weights after the third between calls of the
        # window's own program (PERF.md gives what a 10-step call saves)
        self.steps_per_call = 1
        self.samples_per_call = mix["batch"]
        self.beta1 = opt["beta1"]

    def call(self) -> np.ndarray:
        """The window's own call: returns when the losses are on the host."""
        return self.model.fit_mlm_scanned(self.feed, self.steps_per_call)

    def first_steps(self) -> Dict[str, Any]:
        """Three steps through ``call``; what the comparison reads."""
        import jax

        losses = [float(self.call()[0])]
        b1 = self.beta1
        m = jax.tree.map(lambda s: s["m"], self.model.opt_state,
                         is_leaf=lambda s: isinstance(s, dict) and "m" in s)
        grad_norms = jax.tree.map(lambda a: a / (1.0 - b1),
                                  ref.leaf_norms(m))
        first_moment = jax.tree.map(np.asarray, m)   # to the host: 2 bytes a weight
        losses += [float(self.call()[0]), float(self.call()[0])]
        change = ref.leaf_norms(jax.tree.map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            self.model.params, self._p0))
        self._p0 = None
        return {"losses": losses, "first_moment": first_moment,
                "grad_norms": jax.tree.map(np.asarray, grad_norms),
                "change_norms": jax.tree.map(np.asarray, change)}

    def dispatch_counts(self) -> Dict[str, int]:
        from deeplearning4j_tpu import observe

        return observe.dispatch_summary()

    def free(self) -> None:
        self.model = None
        self.feed = None


def verify(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
           readings: Dict[str, Any], checks: Checks, *, limits=None
           ) -> Dict[str, Any]:
    """Run the reference over the same three steps and hold each number to
    its limit."""
    limits = limits or mix.get("limits") or cfg["limits"]
    got = ref.compare(readings, ref.three_steps(
        cfg, mix, seed, first_moment=readings["first_moment"]))
    for name in ("loss_gap", "grad_norm_gap", "grad_diff", "change_norm_gap"):
        if name in limits:
            checks.add(name, got[name], limits[name])
    note = (f"worst leaves: grad {got['grad_leaf']} diff "
            f"{got['grad_diff_leaf']} change "
            f"{got['change_leaf']}; {got['skipped_leaves']} leaves left out "
            f"of the change")
    checks.require("reference_ran", True, note)
    return got


READ = ("losses", "first_moment", "grad_norms", "change_norms")


def stand_ins(cfg, mix, seed, ctx):
    """What has to come out as not correct, each as readings that ``verify``
    takes in the program's place: the controls (the reference with its
    matmuls in 8 bits) and the faults a training cell can have, planted in
    the reference put in the program's place. Yields (tag, readings, {})."""
    import jax

    for tag, kw in (("control_fp8", {"quant": "fp8"}),
                    ("control_int8", {"quant": "int8"}),
                    ("fault_half_batch", {"half_batch": True})):
        got = ref.three_steps(cfg, mix, seed, keep_moment=True, **kw)
        yield tag, {k: got[k] for k in READ}, {}
    # a step that returns its state unchanged: every loss is the first and
    # no leaf moves (the first gradient is still right)
    got = ref.three_steps(cfg, mix, seed, keep_moment=True)
    stuck = {k: got[k] for k in READ}
    stuck["losses"] = [got["losses"][0]] * len(got["losses"])
    stuck["change_norms"] = jax.tree.map(lambda a: a * 0.0,
                                         got["change_norms"])
    yield "fault_state_unchanged", stuck, {}


def timing(cell, seed: int, seconds: float) -> Dict[str, Any]:
    """Side measurement for PERF.md (``calibrate.py --timing``): the rate
    with one step to a call, as the cell runs, against the 10-step call the
    scanned trainer's users make."""
    import time

    prog = TrainProgram(cell["cfg"], cell["mix"], seed)
    out = {}
    for steps in (1, 10, 1, 10):
        prog.steps_per_call = steps
        prog.call()
        prog.call()
        calls, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            prog.call()
            calls += 1
        rate = calls * steps * cell["mix"]["batch"] / (time.perf_counter() - t0)
        out.setdefault(f"samples_per_s_{steps}_steps_a_call", []).append(rate)
    return out
