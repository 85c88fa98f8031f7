"""MultiLayerNetwork — the sequential model runtime.

Reference parity:
  * org/deeplearning4j/nn/multilayer/MultiLayerNetwork.java (~4.5k lines):
    init/fit/output/score/evaluate, flattened params, listeners.
  * org/deeplearning4j/optimize/Solver.java + solvers/StochasticGradientDescent:
    the per-minibatch optimize step.
  * org/deeplearning4j/nn/updater/MultiLayerUpdater.java: per-layer updater
    blocks over the flattened gradient, regularization + clipping.

TPU-native realization (the SURVEY §4.1 collapse): forward + loss + backward +
regularization + clipping + updater all trace into ONE jitted step function
with donated buffers — the reference's thousands of per-op JNI round trips
per second become one XLA executable launch per iteration. Parameters are a
pytree (list of per-layer dicts); ``params_flat()`` reproduces the
reference's single contiguous parameter view for parity/serde.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import faults, observe

from deeplearning4j_tpu.nn import conf as C
from deeplearning4j_tpu.nn.layers import Layer, build_layer, apply_preprocessor
from deeplearning4j_tpu.nn.updater import Updater
from deeplearning4j_tpu.nn.listeners import (
    TrainingListener, notify_fit_done, notify_preemption)
from deeplearning4j_tpu.ops.losses import get_loss
from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator, ListDataSetIterator
from deeplearning4j_tpu.eval.evaluation import Evaluation, RegressionEvaluation, ROC

logger = logging.getLogger(__name__)

WEIGHT_KEYS = {"W", "RW", "dW", "pW", "Wq", "Wk", "Wv", "Wo"}


def _map_weights(fn, tree, other=None):
    """Apply fn to weight leaves only (regularization targets — the
    reference regularizes weights, not biases/gamma/beta, by default)."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = _map_weights(fn, v, None if other is None else other[k])
            elif k in WEIGHT_KEYS:
                out[k] = fn(v) if other is None else fn(v, other[k])
            else:
                out[k] = v
        return out
    return tree


def _tree_l2_sq_weights(tree) -> jax.Array:
    total = jnp.zeros(())
    if isinstance(tree, dict):
        for k, v in tree.items():
            if isinstance(v, dict):
                total = total + _tree_l2_sq_weights(v)
            elif k in WEIGHT_KEYS:
                total = total + jnp.sum(v.astype(jnp.float32) ** 2)
    return total


def _tree_l1_weights(tree) -> jax.Array:
    total = jnp.zeros(())
    if isinstance(tree, dict):
        for k, v in tree.items():
            if isinstance(v, dict):
                total = total + _tree_l1_weights(v)
            elif k in WEIGHT_KEYS:
                total = total + jnp.sum(jnp.abs(v.astype(jnp.float32)))
    return total


def apply_layer_updates(conf, items, step, normalize_fn):
    """THE per-layer update block, shared by MultiLayerNetwork and
    ComputationGraph: L1/L2 into the gradient, clipping, updater math, weight
    decay (BaseMultiLayerUpdater.update + WeightDecay.applyStep).

    items: iterable of (params, grads, opt_state, updater, layer_conf).
    Returns a list of (new_params, new_opt_state) in input order."""
    out = []
    for p, g, s, upd, lc in items:
        l1 = conf.layer_l1(lc)
        l2 = conf.layer_l2(lc)
        wd = conf.layer_weight_decay(lc)
        if l2:
            g = _map_weights(lambda gw, w: gw + l2 * w, g, p)
        if l1:
            g = _map_weights(lambda gw, w: gw + l1 * jnp.sign(w), g, p)
        g = normalize_fn(g)
        lr = upd.lr(step)
        flat_p, treedef = jax.tree.flatten(p)
        flat_g = treedef.flatten_up_to(g)
        flat_s = treedef.flatten_up_to(s)
        new_p, news = [], []
        for pw, gw, sw in zip(flat_p, flat_g, flat_s):
            # fused step: the registry op's TPU helper runs the whole
            # updater chain as ONE kernel pass per leaf when the tuning
            # table says it wins; generic impl = the identical apply() math
            npw, ns = upd.apply_fused(pw, gw, sw, lr, step)
            new_p.append(npw)
            news.append(ns)
        if wd:
            rebuilt = _map_weights(lambda w, w0: w - lr * wd * w0,
                                   treedef.unflatten(new_p),
                                   treedef.unflatten(flat_p))
            new_p = treedef.flatten_up_to(rebuilt)
        out.append((treedef.unflatten(new_p), treedef.unflatten(news)))
    return out


def aux_losses(new_state):
    """Sum differentiable side losses layers stash in their state under
    ``_aux_loss`` (MoE load-balance loss, nn/moe_layer.py). new_state is a
    list (MultiLayerNetwork) or dict (ComputationGraph) of layer states;
    the scalars are computed inside the loss closure, so gradients flow."""
    states = new_state.values() if isinstance(new_state, dict) else new_state
    total = jnp.zeros(())
    for st in states:
        if isinstance(st, dict) and "_aux_loss" in st:
            total = total + st["_aux_loss"]
    return total


def reg_penalty(conf, items):
    """Score regularization penalty (BaseLayer.calcRegularizationScore).
    items: iterable of (params, layer_conf)."""
    penalty = jnp.zeros(())
    for p, lc in items:
        l1 = conf.layer_l1(lc)
        l2 = conf.layer_l2(lc)
        if l2:
            penalty = penalty + 0.5 * l2 * _tree_l2_sq_weights(p)
        if l1:
            penalty = penalty + l1 * _tree_l1_weights(p)
    return penalty


class MultiLayerNetwork:
    """Sequential network over a MultiLayerConfiguration."""

    def __init__(self, conf: C.MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = []
        itype = conf.input_type
        for i, lc in enumerate(conf.layers):
            pre = conf.preprocessors.get(i)
            if pre is not None and itype is not None:
                if isinstance(pre, C.FeedForwardToCnnPreProcessor):
                    itype = C.InputType.convolutional(pre.height, pre.width, pre.channels)
                elif isinstance(pre, C.CnnToFeedForwardPreProcessor):
                    itype = C.InputType.feed_forward(pre.height * pre.width * pre.channels)
            layer = build_layer(conf, lc, itype or C.InputType.feed_forward(0))
            self.layers.append(layer)
            itype = layer.otype
        self.params: Optional[List[Dict[str, Any]]] = None
        self.net_state: Optional[List[Dict[str, Any]]] = None
        self.opt_state: Optional[List[Any]] = None
        self.updaters: List[Updater] = [conf.layer_updater(lc) for lc in conf.layers]
        self.iteration_count = 0
        self.epoch_count = 0
        # completed batches in the CURRENT epoch — the data cursor exact
        # resume replays from (checkpointed; docs/ROBUSTNESS.md)
        self.batch_in_epoch = 0
        self.listeners: List[TrainingListener] = []
        self.last_batch_size = 0
        self._key = jax.random.key(conf.seed)
        self._jit_cache: Dict[str, Any] = {}
        # loss comes from the terminal layer config
        last = conf.layers[-1] if conf.layers else None
        self._loss_name = getattr(last, "loss", None)
        if hasattr(last, "loss_fn"):  # conf binds its own hyperparameters
            self._loss_fn = last.loss_fn()
        else:
            self._loss_fn = get_loss(self._loss_name) if self._loss_name else None

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[List[Dict[str, Any]]] = None) -> "MultiLayerNetwork":
        """Initialize parameters (MultiLayerNetwork.init())."""
        from deeplearning4j_tpu.nn import dtype as DT

        with DT.precision_scope(self.conf.dtype):
            if params is not None:
                self.params = params
            else:
                key = jax.random.key(self.conf.seed)
                keys = jax.random.split(key, max(len(self.layers), 1))
                self.params = [l.init(k) for l, k in zip(self.layers, keys)]
            self.net_state = [l.init_state() for l in self.layers]
            self.opt_state = [
                jax.tree.map(upd.init_state, p)
                for upd, p in zip(self.updaters, self.params)
            ]
        return self

    def set_listeners(self, *ls: TrainingListener) -> None:
        self.listeners = list(ls)

    def add_listeners(self, *ls: TrainingListener) -> None:
        self.listeners.extend(ls)

    # --------------------------------------------------------------- forward
    def _forward(self, params, net_state, x, mask, *, train: bool, rng,
                 rnn_states=None, tap_input_of: Optional[int] = None):
        """Run preprocessors + layers; returns (out, new_net_state) — or,
        when ``rnn_states`` is given (a list, one entry per layer, None for
        non-recurrent layers), (out, new_net_state, new_rnn_states): the
        tBPTT / rnnTimeStep state-threading path
        (rnnActivateUsingStoredState in the reference)."""
        from deeplearning4j_tpu.nn import dtype as DT

        with DT.precision_scope(self.conf.dtype):
            if DT.needs_cast(self.conf.dtype):
                # mixed policy: bf16 compute against f32 master params — ONE cast
                # chokepoint so grads flow back to the f32 masters
                cd = DT.compute_dtype(self.conf.dtype)
                params = DT.cast_floats(params, cd)
                x = DT.cast_floats(x, cd)
                if rnn_states is not None:
                    rnn_states = DT.cast_floats(rnn_states, cd)
            new_state = []
            new_rnn = [] if rnn_states is not None else None
            tapped = None
            rngs = jax.random.split(rng, max(len(self.layers), 1)) if rng is not None else [None] * len(self.layers)
            for i, layer in enumerate(self.layers):
                x = apply_preprocessor(self.conf.preprocessors.get(i), x)
                if i == tap_input_of:
                    tapped = x
                if rnn_states is not None and hasattr(layer, "apply_with_state"):
                    x = layer._maybe_dropout(x, train=train, rng=rngs[i])
                    x, last = layer.apply_with_state(
                        params[i], x, mask=mask, initial=rnn_states[i])
                    new_rnn.append(last)
                    new_state.append(net_state[i])
                else:
                    x, st, mask = layer.apply(
                        params[i], x, net_state[i], train=train, rng=rngs[i], mask=mask)
                    new_state.append(st)
                    if new_rnn is not None:
                        new_rnn.append(None)
            if DT.needs_cast(self.conf.dtype):
                x = DT.cast_floats(x, jnp.float32)  # loss/eval math stays f32
        if rnn_states is not None:
            return x, new_state, new_rnn
        if tap_input_of is not None:
            return x, new_state, tapped
        return x, new_state

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """Per-layer activations list (MultiLayerNetwork.feedForward) —
        un-jitted debugging path."""
        acts = []
        xj = jnp.asarray(x)
        mask = None
        rngs = jax.random.split(self._key, max(len(self.layers), 1))
        for i, layer in enumerate(self.layers):
            xj = apply_preprocessor(self.conf.preprocessors.get(i), xj)
            xj, _, mask = layer.apply(
                self.params[i], xj, self.net_state[i], train=train, rng=rngs[i], mask=mask)
            acts.append(np.asarray(xj))
        return acts

    # ---------------------------------------------------------------- output
    def output(self, x, mask=None) -> np.ndarray:
        """Inference forward (MultiLayerNetwork.output) — jitted."""
        fn = self._jit_cache.get("output")
        if fn is None:
            @jax.jit
            def fn(params, net_state, x, mask):
                out, _ = self._forward(params, net_state, x, mask, train=False, rng=None)
                return out

            self._jit_cache["output"] = fn
        return np.asarray(fn(self.params, self.net_state, jnp.asarray(x),
                             None if mask is None else jnp.asarray(mask)))

    def predict(self, x) -> np.ndarray:
        return self.output(x).argmax(axis=-1)

    # ------------------------------------------------------ stateful RNN API
    def rnn_time_step(self, x, mask=None) -> np.ndarray:
        """Stateful streaming inference (MultiLayerNetwork.rnnTimeStep):
        feeds (N, T, F) — or (N, F) for a single step — carrying hidden state
        across calls in ``self._rnn_states``."""
        squeeze = False
        x = np.asarray(x)
        if x.ndim == 2:
            x = x[:, None, :]
            squeeze = True
        if not hasattr(self, "_rnn_states") or self._rnn_states is None:
            self._rnn_states = self._zero_rnn_states(x.shape[0], x.dtype)
        fn = self._jit_cache.get("rnn_time_step")
        if fn is None:
            @jax.jit
            def fn(params, net_state, rnn_states, x, mask):
                out, _, new_rnn = self._forward(
                    params, net_state, x, mask, train=False, rng=None,
                    rnn_states=rnn_states)
                return out, new_rnn

            self._jit_cache["rnn_time_step"] = fn
        out, self._rnn_states = fn(self.params, self.net_state, self._rnn_states,
                                   jnp.asarray(x),
                                   None if mask is None else jnp.asarray(mask))
        out = np.asarray(out)
        return out[:, -1] if squeeze else out

    def rnn_clear_previous_state(self) -> None:
        """MultiLayerNetwork.rnnClearPreviousState analog."""
        self._rnn_states = None

    def rnn_get_previous_state(self, layer_idx: int):
        states = getattr(self, "_rnn_states", None)
        return None if states is None else states[layer_idx]

    def _zero_rnn_states(self, batch: int, dtype=np.float32):
        from deeplearning4j_tpu.nn.layers import BidirectionalImpl

        states = []
        for layer in self.layers:
            if isinstance(layer, BidirectionalImpl):
                # reference rnnTimeStep throws UnsupportedOperationException
                # for bidirectional layers — backward pass needs the future
                raise ValueError(
                    "stateful RNN state (rnn_time_step / tBPTT) is not "
                    "supported with Bidirectional layers")
            if hasattr(layer, "zero_state"):
                states.append(layer.zero_state(batch, dtype))
            else:
                states.append(None)
        return states

    # ------------------------------------------------------------- train step
    def _loss_from_out(self, out, labels, lmask):
        if self._loss_fn is None:
            raise ValueError("terminal layer has no loss configured")
        return self._loss_fn(out, labels, lmask)

    def _apply_updates(self, params, grads, opt_state, step):
        new_items = apply_layer_updates(
            self.conf,
            zip(params, grads, opt_state, self.updaters, self.conf.layers),
            step, self._normalize_gradient)
        return [p for p, _ in new_items], [s for _, s in new_items]

    def _reg_penalty(self, params):
        return reg_penalty(self.conf, zip(params, self.conf.layers))

    def _make_train_step(self):
        last_lc = self.conf.layers[-1] if self.conf.layers else None
        center = isinstance(last_lc, C.CenterLossOutputLayer)

        def train_step(params, opt_state, net_state, step, key, features, labels, fmask, lmask):
            def loss_fn(p):
                if center:
                    # CenterLossOutputLayer: tap the features feeding the
                    # output layer and add λ·½‖f − c_y‖²; gradients flow both
                    # into the centers (params[-1]["centers"]) and back into
                    # the feature extractor — reference semantics.
                    out, new_state, feats = self._forward(
                        p, net_state, features, fmask, train=True, rng=key,
                        tap_input_of=len(self.layers) - 1)
                    loss = self._loss_from_out(out, labels, lmask)
                    f32 = jnp.promote_types(jnp.float32, feats.dtype)
                    f = feats.astype(f32)
                    centers = p[-1]["centers"].astype(f32)
                    y_idx = jnp.argmax(labels, axis=-1)
                    # decoupled center loss: λ weighs the FEATURE pull toward
                    # (detached) centers; α weighs the CENTER pull toward
                    # (detached) features — the gradient α(c_y − f̄) is the
                    # reference's moving-average center update c←c−α(c−f̄)
                    # realized through the optimizer (CenterLossOutputLayer
                    # alpha/lambda semantics).
                    sg = jax.lax.stop_gradient
                    d_feat = f - sg(centers[y_idx])
                    d_ctr = sg(f) - centers[y_idx]
                    loss = (loss
                            + 0.5 * last_lc.lambda_ * jnp.mean(
                                jnp.sum(jnp.square(d_feat), axis=-1))
                            + 0.5 * last_lc.alpha * jnp.mean(
                                jnp.sum(jnp.square(d_ctr), axis=-1)))
                    return loss, new_state
                out, new_state = self._forward(p, net_state, features, fmask, train=True, rng=key)
                loss = self._loss_from_out(out, labels, lmask)
                return loss + aux_losses(new_state), new_state

            (loss, new_net_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(params, grads, opt_state, step)
            return new_params, new_opt, new_net_state, loss + self._reg_penalty(params)

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _make_train_step_tbptt(self):
        """Truncated-BPTT step: same fused step, but RNN state enters as an
        input and leaves as an output — gradients truncate at the segment
        boundary because the incoming state is a constant w.r.t. this
        segment's params (reference MultiLayerNetwork.doTruncatedBPTT)."""

        def train_step(params, opt_state, net_state, rnn_states, step, key,
                       features, labels, fmask, lmask):
            def loss_fn(p):
                out, new_state, new_rnn = self._forward(
                    p, net_state, features, fmask, train=True, rng=key,
                    rnn_states=rnn_states)
                loss = self._loss_from_out(out, labels, lmask)
                return loss + aux_losses(new_state), (new_state, new_rnn)

            (loss, (new_net_state, new_rnn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(params, grads, opt_state, step)
            return new_params, new_opt, new_net_state, new_rnn, loss + self._reg_penalty(params)

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _fit_tbptt_batch(self, ds, step_fn):
        """Slice the time axis into tBPTT segments, carrying RNN state."""
        fwd = self.conf.tbptt_fwd_length
        if ds.labels.ndim < 3:
            # reference tBPTT requires time-series (3D) labels; a per-sequence
            # label would get one full update per segment against prefixes
            raise ValueError(
                "tBPTT requires 3-D time-series labels (N, T, C); got shape "
                f"{ds.labels.shape} — use standard backprop for per-sequence labels")
        T = ds.features.shape[1]
        rnn_states = self._zero_rnn_states(ds.features.shape[0])
        segments = list(range(0, T, fwd))
        for i, t0 in enumerate(segments):
            t1 = min(t0 + fwd, T)
            seg_x = jnp.asarray(ds.features[:, t0:t1])
            seg_y = jnp.asarray(ds.labels[:, t0:t1])
            seg_fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask[:, t0:t1])
            seg_lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask[:, t0:t1])
            self._key, sub = jax.random.split(self._key)
            (self.params, self.opt_state, self.net_state, rnn_states, loss) = step_fn(
                self.params, self.opt_state, self.net_state, rnn_states,
                jnp.asarray(self.iteration_count, jnp.int32), sub,
                seg_x, seg_y, seg_fm, seg_lm)
            # the reference advances the iteration once per optimize call, i.e.
            # per tBPTT segment (Adam bias-correction t, LR schedules); fit()
            # adds the final +1 covering the last segment
            if i < len(segments) - 1:
                self.iteration_count += 1
        return loss

    def _normalize_gradient(self, g):
        """GradientNormalization enum semantics (BaseMultiLayerUpdater)."""
        kind = self.conf.gradient_normalization
        if not kind:
            return g
        thr = self.conf.gradient_normalization_threshold
        leaves = jax.tree.leaves(g)
        if kind == "renormalize_l2_per_layer":
            norm = jnp.sqrt(sum(jnp.sum(l**2) for l in leaves) + 1e-12)
            return jax.tree.map(lambda l: l / norm, g)
        if kind == "clip_element_wise_absolute_value":
            return jax.tree.map(lambda l: jnp.clip(l, -thr, thr), g)
        if kind == "clip_l2_per_layer":
            norm = jnp.sqrt(sum(jnp.sum(l**2) for l in leaves) + 1e-12)
            scale = jnp.minimum(1.0, thr / norm)
            return jax.tree.map(lambda l: l * scale, g)
        if kind == "clip_l2_per_param_type":
            def clip_one(l):
                n = jnp.sqrt(jnp.sum(l**2) + 1e-12)
                return l * jnp.minimum(1.0, thr / n)
            return jax.tree.map(clip_one, g)
        raise ValueError(f"unknown gradient normalization '{kind}'")

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32) -> None:
        """fit(DataSetIterator | DataSet | (features, labels)).

        MultiLayerNetwork.fit analog; each minibatch runs the single fused
        step function. Arrays are device-put once per batch; donation recycles
        param/optimizer buffers in place (the workspace-arena analog).
        """
        if labels is not None:
            data = ListDataSetIterator(DataSet(data, labels), batch_size=batch_size)
        elif isinstance(data, DataSet):
            data = ListDataSetIterator(data, batch_size=batch_size)

        tbptt = (self.conf.backprop_type == "tbptt" and self.conf.tbptt_fwd_length > 0)
        cache_name = "train_step_tbptt" if tbptt else "train_step"
        step_fn = self._jit_cache.get(cache_name)
        if step_fn is None:
            step_fn = (self._make_train_step_tbptt() if tbptt
                       else self._make_train_step())
            self._jit_cache[cache_name] = step_fn

        _m = observe.metrics()
        _steps_c = _m.counter("dl4j_tpu_train_steps_total", model="mln")
        _ex_c = _m.counter("dl4j_tpu_train_examples_total", model="mln")
        _xfer_c = _m.counter("dl4j_tpu_host_to_device_transfers_total",
                             model="mln")
        _step_h = _m.histogram("dl4j_tpu_train_step_seconds", model="mln")
        for _ in range(epochs):
            for lst in self.listeners:
                lst.on_epoch_start(self)
            t_prev = time.perf_counter()
            n_steps = 0
            # nonzero only when resuming mid-epoch from a checkpoint: the
            # first `skip` batches were already consumed by the killed run
            skip = self.batch_in_epoch
            for bi, ds in enumerate(data):
                if bi < skip:
                    continue
                # preemption (docs/ROBUSTNESS.md): the injected fault is a
                # HARD pod kill (raise — the supervisor restores+resumes);
                # the flag is the SOFT SIGTERM path (final snapshot, clean
                # exit). Both checked at the step boundary, off-trace.
                faults.maybe_fail("preemption")
                if faults.preemption_requested():
                    notify_preemption(self, self.listeners)
                    return
                self.last_batch_size = ds.num_examples()
                # recompile ledger: a new feed shape/dtype signature on the
                # cached jitted step is a silent XLA retrace — record it
                observe.note_jit_signature(
                    step_fn, graph="mln", key=cache_name,
                    signature=observe.signature_of(
                        x=ds.features, y=ds.labels, fm=ds.features_mask,
                        lm=ds.labels_mask))
                # host-side reference only (no copy): StatsListener's
                # activation charts feed_forward this batch on demand
                self._last_features = ds.features
                if tbptt:
                    loss = self._fit_tbptt_batch(ds, step_fn)
                else:
                    self._key, sub = jax.random.split(self._key)
                    self.params, self.opt_state, self.net_state, loss = step_fn(
                        self.params, self.opt_state, self.net_state,
                        jnp.asarray(self.iteration_count, jnp.int32), sub,
                        jnp.asarray(ds.features), jnp.asarray(ds.labels),
                        None if ds.features_mask is None else jnp.asarray(ds.features_mask),
                        None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
                    )
                # keep the device array — float() would force a host sync per
                # step and stall async dispatch; score() converts lazily
                self._score = loss
                self.iteration_count += 1
                self.batch_in_epoch = bi + 1  # cursor BEFORE listeners save
                # inter-step latency on the monotonic clock (first delta
                # includes compile); all telemetry is host-side, off-trace
                now = time.perf_counter()
                _step_h.observe(now - t_prev)
                t_prev = now
                n_steps += 1
                _steps_c.inc()
                _ex_c.inc(ds.num_examples())
                _xfer_c.inc(2 + (ds.features_mask is not None)
                            + (ds.labels_mask is not None))
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count, self.epoch_count, loss)
            self.batch_in_epoch = 0
            self.epoch_count += 1
            observe.log_event("train_epoch", model="mln",
                              epoch=self.epoch_count, steps=n_steps)
            for lst in self.listeners:
                lst.on_epoch_end(self)
        notify_fit_done(self, self.listeners)

    def fit_scanned(self, features, labels, steps: Optional[int] = None) -> np.ndarray:
        """Run many fused train steps in ONE XLA call (lax.scan over the
        train step) — the TPU-native inner loop: zero host dispatch between
        steps, donated carry, schedules/iteration advancing on-device.

        Two modes:
          * ``steps`` given — train repeatedly on the single device-resident
            batch (throughput/benchmark mode).
          * ``steps`` None — ``features``/``labels`` carry a leading
            [steps, batch, ...] axis of per-step minibatches (the
            device-resident-epoch pattern: stage the epoch to HBM once, scan).

        Masks are not supported on this path (use fit()). Returns the
        per-step loss array. Reference analog: there is none — the per-op
        JNI dispatch makes a fused multi-step loop impossible there; this is
        the whole-graph-compile dividend (SURVEY §8.1)."""
        step_fn = self._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = self._make_train_step()
            self._jit_cache["train_step"] = step_fn
        per_step_data = steps is None
        shape = np.shape(features)
        n_steps = int(shape[0]) if per_step_data else int(steps)
        self.last_batch_size = int(shape[1] if per_step_data else shape[0])

        cache_key = ("fit_scanned", per_step_data, n_steps)
        many = self._jit_cache.get(cache_key)
        if many is None:
            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def many(params, opt_state, net_state, start, key, xs, ys):
                def body(carry, it):
                    p, o, s = carry
                    if per_step_data:
                        i, x, y = it
                    else:
                        i, x, y = it, xs, ys
                    p, o, s, loss = step_fn(p, o, s, i, jax.random.fold_in(key, i),
                                            x, y, None, None)
                    return (p, o, s), loss
                idx = start + jnp.arange(n_steps, dtype=jnp.int32)
                sc_xs = (idx, xs, ys) if per_step_data else idx
                (p, o, s), losses = jax.lax.scan(body, (params, opt_state, net_state), sc_xs)
                return p, o, s, losses

            self._jit_cache[cache_key] = many
        with observe.scanned_call(
                "mln", n_steps, n_steps * self.last_batch_size) as call:
            with call.dispatch():
                xs = jnp.asarray(features)
                ys = jnp.asarray(labels)
                observe.note_jit_signature(
                    many, graph="mln", key="fit_scanned",
                    signature=observe.signature_of(x=xs, y=ys))
                self._key, sub = jax.random.split(self._key)
                self.params, self.opt_state, self.net_state, losses = many(
                    self.params, self.opt_state, self.net_state,
                    jnp.asarray(self.iteration_count, jnp.int32), sub, xs, ys)
                self._score = losses[-1]
            start = self.iteration_count
            self.iteration_count += n_steps
            observe.metrics().counter(
                "dl4j_tpu_host_to_device_transfers_total", model="mln").inc(2)
            with call.read():
                losses = np.asarray(losses)  # host sync: the chunk is done here
        # listeners fire AFTER the fused chunk, once per inner step with the
        # recorded loss — coarser timing than fit() (params are only current
        # as of the chunk end) but checkpoint/score listeners keep working on
        # the fast path instead of silently not firing (round-2 weak #8).
        # Iteration-major order so multi-listener interleaving matches fit()
        for k in range(n_steps):
            for lst in self.listeners:
                lst.iteration_done(self, start + k + 1, self.epoch_count,
                                   float(losses[k]))
        return losses

    def score(self, ds: Optional[DataSet] = None) -> float:
        """Loss on a dataset, or last training score (MultiLayerNetwork.score)."""
        if ds is None:
            s = getattr(self, "_score", float("nan"))
            return float(s)
        out = self.output(ds.features, ds.features_mask)
        loss = self._loss_fn(
            jnp.asarray(out), jnp.asarray(ds.labels),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask))
        return float(loss)

    # -------------------------------------------------------------- evaluate
    def evaluate(self, iterator, evaluation=None) -> Evaluation:
        """evaluate(DataSetIterator) -> Evaluation (net.evaluate analog)."""
        e = evaluation if evaluation is not None else Evaluation()
        if isinstance(iterator, DataSet):
            iterator = ListDataSetIterator(iterator, batch_size=256)
        for ds in iterator:
            out = self.output(ds.features, ds.features_mask)
            e.eval(ds.labels, out, ds.labels_mask)
        return e

    def evaluate_regression(self, iterator) -> RegressionEvaluation:
        return self.evaluate(iterator, RegressionEvaluation())

    def evaluate_roc(self, iterator) -> ROC:
        return self.evaluate(iterator, ROC())

    # ------------------------------------------------------- flattened params
    def params_flat(self) -> np.ndarray:
        """Single flat parameter vector (MultiLayerNetwork.params()).

        The reference stores ALL params as views into one contiguous buffer;
        we reproduce the export for serde/parity. Order: layer order, then
        sorted param keys within a layer (deterministic)."""
        leaves = []
        for p in self.params:
            leaves.extend(_sorted_leaves(p))
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_params_flat(self, flat: np.ndarray) -> None:
        flat = np.asarray(flat)
        offset = 0
        new_params = []
        for p in self.params:
            new_p, offset = _unflatten_like(p, flat, offset)
            new_params.append(new_p)
        if offset != flat.size:
            raise ValueError(f"param vector length {flat.size} != model size {offset}")
        self.params = jax.tree.map(jnp.asarray, new_params)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for p in self.params for l in jax.tree.leaves(p))

    # ------------------------------------------------------- updater state io
    def updater_state_flat(self) -> np.ndarray:
        leaves = []
        for s in self.opt_state:
            leaves.extend(_sorted_leaves(s))
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_updater_state_flat(self, flat: np.ndarray) -> None:
        flat = np.asarray(flat)
        offset = 0
        new_states = []
        for s in self.opt_state:
            new_s, offset = _unflatten_like(s, flat, offset)
            new_states.append(new_s)
        self.opt_state = jax.tree.map(jnp.asarray, new_states)


def _sorted_leaves(tree) -> List[Any]:
    """Deterministic (sorted-key DFS) leaf order for flat export."""
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            v = tree[k]
            if isinstance(v, dict):
                out.extend(_sorted_leaves(v))
            else:
                out.append(v)
    return out


def _unflatten_like(tree, flat, offset):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            v = tree[k]
            if isinstance(v, dict):
                out[k], offset = _unflatten_like(v, flat, offset)
            else:
                n = int(np.prod(v.shape)) if v.shape else 1
                out[k] = flat[offset : offset + n].reshape(v.shape).astype(np.asarray(v).dtype)
                offset += n
        # preserve original insertion order of the source dict
        return {k: out[k] for k in tree}, offset
    return tree, offset
