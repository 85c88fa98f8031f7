"""ComputationGraph — DAG networks with multiple inputs/outputs.

Reference parity:
  * org/deeplearning4j/nn/graph/ComputationGraph.java (~5k lines) and
    conf/ComputationGraphConfiguration.java (GraphBuilder: addInputs /
    addLayer(name, conf, inputs...) / addVertex / setOutputs).
  * graph/vertex/impl/* — MergeVertex, ElementWiseVertex, SubsetVertex,
    ScaleVertex, ShiftVertex, L2NormalizeVertex, PreprocessorVertex,
    StackVertex, UnstackVertex, ReshapeVertex.

TPU-native realization: same collapse as MultiLayerNetwork — the whole DAG
(forward + losses at all output layers + backward + updaters) traces into one
jitted XLA step. Topological order is fixed at build time (config is static),
so the traced program is a straight-line fused computation.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import faults, observe

from deeplearning4j_tpu.nn import conf as C
from deeplearning4j_tpu.nn.layers import Layer, build_layer, apply_preprocessor
from deeplearning4j_tpu.nn.updater import Updater, get_updater
from deeplearning4j_tpu.nn.listeners import (
    TrainingListener, notify_fit_done, notify_preemption)
from deeplearning4j_tpu.nn.multilayer import (
    _map_weights, _tree_l1_weights, _tree_l2_sq_weights, _sorted_leaves,
    _unflatten_like, apply_layer_updates, aux_losses, reg_penalty,
)
from deeplearning4j_tpu.ops.losses import get_loss
from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator, ListDataSetIterator
from deeplearning4j_tpu.eval.evaluation import Evaluation

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Graph vertices (conf/graph/*Vertex + graph/vertex/impl/*)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphVertex:
    """Base non-layer vertex."""

    def apply(self, inputs: List[jax.Array]):
        raise NotImplementedError

    def output_type(self, itypes: List[C.InputType]) -> C.InputType:
        return itypes[0]

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d)
        cls = VERTEX_TYPES[d.pop("@type")]
        for k, v in list(d.items()):
            if isinstance(v, list):
                d[k] = tuple(v)
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class MergeVertex(GraphVertex):
    """MergeVertex.java: concat along the feature/channel axis."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=-1)

    def output_type(self, itypes):
        t0 = itypes[0]
        if t0.kind == "convolutional":
            return C.InputType.convolutional(t0.height, t0.width,
                                             sum(t.channels for t in itypes))
        if t0.kind == "recurrent":
            return C.InputType.recurrent(sum(t.size for t in itypes), t0.timesteps)
        return C.InputType.feed_forward(sum(t.flat_size() for t in itypes))


@dataclasses.dataclass(frozen=True)
class ElementWiseVertex(GraphVertex):
    """ElementWiseVertex.java: Add | Subtract | Product | Average | Max."""

    op: str = "add"

    def apply(self, inputs):
        op = self.op.lower()
        if op == "add":
            out = inputs[0]
            for x in inputs[1:]:
                out = out + x
            return out
        if op == "subtract":
            return inputs[0] - inputs[1]
        if op == "product":
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
            return out
        if op == "average":
            return sum(inputs) / len(inputs)
        if op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
            return out
        if op == "min":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.minimum(out, x)
            return out
        raise ValueError(f"unknown ElementWiseVertex op {self.op}")



@dataclasses.dataclass(frozen=True)
class DotProductVertex(GraphVertex):
    """Keras Dot merge: batched contraction of two inputs along ``axes``
    (an int applied to both sides; negative allowed), optional L2
    normalization first (cosine proximity)."""

    axes: int = -1
    normalize: bool = False

    def apply(self, inputs):
        a, b = inputs
        ax = self.axes
        if self.normalize:
            a = a / jnp.maximum(jnp.linalg.norm(a, axis=ax, keepdims=True),
                                1e-12)
            b = b / jnp.maximum(jnp.linalg.norm(b, axis=ax, keepdims=True),
                                1e-12)
        axa, axb = ax % a.ndim, ax % b.ndim
        out = jax.vmap(lambda u, v: jnp.tensordot(
            u, v, axes=((axa - 1,), (axb - 1,))))(a, b)
        if out.ndim == 1:
            out = out[:, None]  # keras keeps a trailing dim for vector dots
        return out

    def output_type(self, itypes):
        a, b = itypes
        if a.kind == "feedforward" or (a.kind == "recurrent"
                                       and self.axes in (-1, 2)):
            # vector dot -> (N, 1); (N,T,F)x(N,S,F) axes=-1 -> (N,T,S)
            if a.kind == "feedforward":
                return C.InputType.feed_forward(1)
            return C.InputType.recurrent(
                b.timesteps if b.timesteps else -1, a.timesteps)
        raise NotImplementedError(
            f"DotProductVertex shape inference for {a.kind} inputs with "
            f"axes={self.axes}")


@dataclasses.dataclass(frozen=True)
class SubsetVertex(GraphVertex):
    """SubsetVertex.java: feature-axis slice [from, to] inclusive."""

    from_idx: int = 0
    to_idx: int = 0

    def apply(self, inputs):
        return inputs[0][..., self.from_idx : self.to_idx + 1]

    def output_type(self, itypes):
        n = self.to_idx - self.from_idx + 1
        t = itypes[0]
        if t.kind == "recurrent":
            return C.InputType.recurrent(n, t.timesteps)
        return C.InputType.feed_forward(n)


@dataclasses.dataclass(frozen=True)
class ScaleVertex(GraphVertex):
    """ScaleVertex.java: multiply by a constant."""

    scale: float = 1.0

    def apply(self, inputs):
        return inputs[0] * self.scale


@dataclasses.dataclass(frozen=True)
class ShiftVertex(GraphVertex):
    """ShiftVertex.java: add a constant."""

    shift: float = 0.0

    def apply(self, inputs):
        return inputs[0] + self.shift


@dataclasses.dataclass(frozen=True)
class L2NormalizeVertex(GraphVertex):
    """L2NormalizeVertex.java: x / ||x||₂ along the feature axis."""

    eps: float = 1e-8

    def apply(self, inputs):
        x = inputs[0]
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + self.eps)
        return x / norm


@dataclasses.dataclass(frozen=True)
class StackVertex(GraphVertex):
    """StackVertex.java: stack along batch axis (axis 0)."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=0)


@dataclasses.dataclass(frozen=True)
class ReshapeVertex(GraphVertex):
    """ReshapeVertex.java."""

    shape: Tuple[int, ...] = ()

    def apply(self, inputs):
        return jnp.reshape(inputs[0], self.shape)


@dataclasses.dataclass(frozen=True)
class UnstackVertex(GraphVertex):
    """UnstackVertex.java: inverse of StackVertex — slice subrange
    [from·size : (from+1)·size] of the batch axis (stack_size = number of
    stacked inputs the producing StackVertex concatenated)."""

    from_idx: int = 0
    stack_size: int = 1

    def apply(self, inputs):
        x = inputs[0]
        n = x.shape[0] // self.stack_size
        return x[self.from_idx * n:(self.from_idx + 1) * n]


@dataclasses.dataclass(frozen=True)
class DuplicateToTimeSeriesVertex(GraphVertex):
    """DuplicateToTimeSeriesVertex.java: broadcast a (N, F) feed-forward
    input across the timesteps of a reference recurrent input — inputs are
    (value, time_reference)."""

    def apply(self, inputs):
        val, ref = inputs
        t = ref.shape[1]
        return jnp.broadcast_to(val[:, None, :], (val.shape[0], t, val.shape[1]))

    def output_type(self, itypes):
        return C.InputType.recurrent(itypes[0].flat_size(),
                                     itypes[1].timesteps)


@dataclasses.dataclass(frozen=True)
class LastTimeStepVertex(GraphVertex):
    """LastTimeStepVertex.java: (N, T, F) → (N, F) last step. NOTE: vertices
    do not receive masks in this engine; for masked sequences use the
    LastTimeStep LAYER wrapper (conf.LastTimeStep), which does."""

    def apply(self, inputs):
        return inputs[0][:, -1]

    def output_type(self, itypes):
        return C.InputType.feed_forward(itypes[0].size)


@dataclasses.dataclass(frozen=True)
class FlattenVertex(GraphVertex):
    """Batch-preserving flatten (PreprocessorVertex(CnnToFeedForward)
    analog, but feature-major order preserved — used by the Keras
    functional import where activations are already NHWC like Keras's)."""

    def apply(self, inputs):
        x = inputs[0]
        return jnp.reshape(x, (x.shape[0], -1))

    def output_type(self, itypes):
        return C.InputType.feed_forward(itypes[0].flat_size())


VERTEX_TYPES = {
    c.__name__: c
    for c in [MergeVertex, ElementWiseVertex, SubsetVertex, ScaleVertex,
              ShiftVertex, L2NormalizeVertex, StackVertex, ReshapeVertex,
              FlattenVertex, UnstackVertex, DuplicateToTimeSeriesVertex,
              LastTimeStepVertex, DotProductVertex]
}


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _GraphNode:
    name: str
    kind: str  # 'layer' | 'vertex'
    layer: Optional[C.LayerConf] = None
    vertex: Optional[GraphVertex] = None
    inputs: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ComputationGraphConfiguration:
    """ComputationGraphConfiguration.java analog."""

    network_inputs: List[str] = dataclasses.field(default_factory=list)
    network_outputs: List[str] = dataclasses.field(default_factory=list)
    nodes: List[_GraphNode] = dataclasses.field(default_factory=list)
    input_types: Dict[str, C.InputType] = dataclasses.field(default_factory=dict)
    seed: int = 0
    updater: Any = None
    activation: str = "identity"
    weight_init: str = "xavier"
    l1: float = 0.0
    l2: float = 0.0
    weight_decay: float = 0.0
    dtype: str = "float32"
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    tbptt_fwd_length: int = -1
    tbptt_back_length: int = -1
    backprop_type: str = "standard"

    # reuse MultiLayerConfiguration's per-layer default resolution
    layer_activation = C.MultiLayerConfiguration.layer_activation
    layer_weight_init = C.MultiLayerConfiguration.layer_weight_init
    layer_updater = C.MultiLayerConfiguration.layer_updater
    layer_l1 = C.MultiLayerConfiguration.layer_l1
    layer_l2 = C.MultiLayerConfiguration.layer_l2
    layer_weight_decay = C.MultiLayerConfiguration.layer_weight_decay

    def __post_init__(self):
        if self.updater is None:
            from deeplearning4j_tpu.nn.updater import Adam

            self.updater = Adam()

    def to_json(self) -> str:
        return json.dumps({
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "nodes": [
                {"name": n.name, "kind": n.kind,
                 "layer": n.layer.to_dict() if n.layer else None,
                 "vertex": n.vertex.to_dict() if n.vertex else None,
                 "inputs": n.inputs}
                for n in self.nodes
            ],
            "input_types": {k: v.to_dict() for k, v in self.input_types.items()},
            "seed": self.seed,
            "updater": {"__updater__": get_updater(self.updater).to_dict()},
            "activation": self.activation,
            "weight_init": self.weight_init,
            "l1": self.l1, "l2": self.l2, "weight_decay": self.weight_decay,
            "dtype": self.dtype,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)
        conf = ComputationGraphConfiguration(
            network_inputs=d["network_inputs"],
            network_outputs=d["network_outputs"],
            nodes=[
                _GraphNode(
                    name=nd["name"], kind=nd["kind"],
                    layer=C.LayerConf.from_dict(nd["layer"]) if nd["layer"] else None,
                    vertex=GraphVertex.from_dict(nd["vertex"]) if nd["vertex"] else None,
                    inputs=list(nd["inputs"]))
                for nd in d["nodes"]
            ],
            input_types={k: C.InputType.from_dict(v) for k, v in d["input_types"].items()},
            seed=d.get("seed", 0),
            updater=Updater.from_dict(d["updater"]["__updater__"]),
            activation=d.get("activation", "identity"),
            weight_init=d.get("weight_init", "xavier"),
            l1=d.get("l1", 0.0), l2=d.get("l2", 0.0),
            weight_decay=d.get("weight_decay", 0.0),
            dtype=d.get("dtype", "float32"),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
        )
        return conf


class GraphBuilder:
    """ComputationGraphConfiguration.GraphBuilder analog (fluent)."""

    def __init__(self) -> None:
        self._conf = ComputationGraphConfiguration()

    def seed(self, s: int):
        self._conf.seed = s
        return self

    def updater(self, u):
        self._conf.updater = u
        return self

    def activation(self, a: str):
        self._conf.activation = a
        return self

    def weight_init(self, w: str):
        self._conf.weight_init = w
        return self

    def l1(self, v: float):
        self._conf.l1 = v
        return self

    def l2(self, v: float):
        self._conf.l2 = v
        return self

    def weight_decay(self, v: float):
        self._conf.weight_decay = v
        return self

    def dtype(self, d: str):
        self._conf.dtype = d
        return self

    def gradient_normalization(self, kind: str, threshold: float = 1.0):
        self._conf.gradient_normalization = kind
        self._conf.gradient_normalization_threshold = threshold
        return self

    def graph_builder(self):
        return self

    def add_inputs(self, *names: str):
        self._conf.network_inputs.extend(names)
        return self

    def set_input_types(self, **types: C.InputType):
        self._conf.input_types.update(types)
        return self

    def add_layer(self, name: str, layer: C.LayerConf, *inputs: str):
        self._conf.nodes.append(_GraphNode(name=name, kind="layer", layer=layer,
                                           inputs=list(inputs)))
        return self

    def add_vertex(self, name: str, vertex, *inputs: str):
        # parameterized vertices (reference AttentionVertex et al. extend
        # SameDiffVertex WITH params) are LayerConf instances here — route
        # them to the layer path, which owns params/state
        if isinstance(vertex, C.LayerConf):
            return self.add_layer(name, vertex, *inputs)
        self._conf.nodes.append(_GraphNode(name=name, kind="vertex", vertex=vertex,
                                           inputs=list(inputs)))
        return self

    def set_outputs(self, *names: str):
        self._conf.network_outputs.extend(names)
        return self

    def build(self) -> ComputationGraphConfiguration:
        return self._conf


def graph_builder() -> GraphBuilder:
    return GraphBuilder()


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------


class ComputationGraph:
    """DAG network runtime (ComputationGraph.java analog)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self._order = self._toposort()
        # shape inference over the DAG (ComputationGraphConfiguration
        # addPreProcessors/getLayerActivationTypes analog)
        self._itypes: Dict[str, C.InputType] = {}
        self.layers: Dict[str, Layer] = {}
        self._net_conf_view = self._as_mlc()
        for name in conf.network_inputs:
            it = conf.input_types.get(name, C.InputType.feed_forward(0))
            if it.kind == "convolutionalflat":
                it = C.InputType.convolutional(it.height, it.width, it.channels)
            self._itypes[name] = it
        for node in self._order:
            in_types = [self._itypes[i] for i in node.inputs]
            if node.kind == "vertex":
                self._itypes[node.name] = node.vertex.output_type(in_types)
            else:
                itype, lc = self._infer_layer(node, in_types[0])
                node.layer = lc
                layer = build_layer(self._net_conf_view, lc, itype)
                self.layers[node.name] = layer
                self._itypes[node.name] = layer.otype
        self.params: Optional[Dict[str, Dict[str, Any]]] = None
        self.net_state: Optional[Dict[str, Dict[str, Any]]] = None
        self.opt_state: Optional[Dict[str, Any]] = None
        self.iteration_count = 0
        self.epoch_count = 0
        # completed batches in the CURRENT epoch — the data cursor exact
        # resume replays from (checkpointed; docs/ROBUSTNESS.md)
        self.batch_in_epoch = 0
        self.listeners: List[TrainingListener] = []
        self.last_batch_size = 0
        self._key = jax.random.key(conf.seed)
        self._jit_cache: Dict[Any, Any] = {}
        self._output_layers = [
            n for n in conf.network_outputs
            if getattr(self._node(n).layer, "loss", None) is not None
        ]

    def _as_mlc(self) -> C.MultiLayerConfiguration:
        c = self.conf
        return C.MultiLayerConfiguration(
            seed=c.seed, updater=c.updater, activation=c.activation,
            weight_init=c.weight_init, l1=c.l1, l2=c.l2,
            weight_decay=c.weight_decay, dtype=c.dtype,
            gradient_normalization=c.gradient_normalization,
            gradient_normalization_threshold=c.gradient_normalization_threshold,
        )

    def _node(self, name: str) -> _GraphNode:
        for n in self.conf.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def _toposort(self) -> List[_GraphNode]:
        done = set(self.conf.network_inputs)
        remaining = list(self.conf.nodes)
        order = []
        while remaining:
            progress = False
            for n in list(remaining):
                if all(i in done for i in n.inputs):
                    order.append(n)
                    done.add(n.name)
                    remaining.remove(n)
                    progress = True
            if not progress:
                cycle = [n.name for n in remaining]
                raise ValueError(f"graph has a cycle or missing inputs: {cycle}")
        return order

    def _infer_layer(self, node: _GraphNode, itype: C.InputType):
        """Fill n_in and adapt conv->ff shapes, per-node (the reference's
        auto preprocessor insertion)."""
        lc = node.layer
        needs_ff = isinstance(lc, (C.DenseLayer, C.OutputLayer, C.EmbeddingLayer))
        if itype.kind in ("convolutional", "convolutional3d") and needs_ff:
            itype = C.InputType.feed_forward(itype.flat_size())
            node.kind = "layer"  # unchanged; flattening applied at runtime
            setattr(node, "_flatten_input", True)
        fake = C.MultiLayerConfiguration(layers=[lc], input_type=itype)
        itype2, lc2 = C._adapt(fake, 0, itype, lc)
        return itype2, lc2

    # ------------------------------------------------------------------ init
    def init(self, params=None) -> "ComputationGraph":
        from deeplearning4j_tpu.nn import dtype as DT

        with DT.precision_scope(self.conf.dtype):
            if params is not None:
                self.params = params
            else:
                key = jax.random.key(self.conf.seed)
                names = [n.name for n in self._order if n.kind == "layer"]
                keys = jax.random.split(key, max(len(names), 1))
                self.params = {
                    name: self.layers[name].init(k) for name, k in zip(names, keys)
                }
            self.net_state = {name: l.init_state() for name, l in self.layers.items()}
            self.opt_state = {}
            for name, l in self.layers.items():
                upd = self.conf.layer_updater(l.lc)
                self.opt_state[name] = jax.tree.map(upd.init_state, self.params[name])
        return self

    def set_listeners(self, *ls: TrainingListener) -> None:
        self.listeners = list(ls)

    # --------------------------------------------------------------- forward
    def _forward(self, params, net_state, inputs: Dict[str, Any], masks,
                 *, train: bool, rng, rnn_states: Optional[Dict[str, Any]] = None):
        """When ``rnn_states`` is given (node-name → carried RNN state, None
        for non-recurrent nodes) returns (acts, new_state, new_rnn_states) —
        the ComputationGraph rnnTimeStep / tBPTT state-threading path."""
        from deeplearning4j_tpu.nn import dtype as DT

        with DT.precision_scope(self.conf.dtype):
            if DT.needs_cast(self.conf.dtype):
                # mixed policy: bf16 compute against f32 master params — ONE cast
                # chokepoint so grads flow back to the f32 masters
                cd = DT.compute_dtype(self.conf.dtype)
                params = DT.cast_floats(params, cd)
                inputs = DT.cast_floats(inputs, cd)
                if rnn_states is not None:
                    rnn_states = DT.cast_floats(rnn_states, cd)
            acts: Dict[str, Any] = dict(inputs)
            act_masks: Dict[str, Any] = dict(masks or {})
            new_state: Dict[str, Any] = {}
            new_rnn: Optional[Dict[str, Any]] = (
                {} if rnn_states is not None else None)
            layer_names = [n.name for n in self._order if n.kind == "layer"]
            rngs = (jax.random.split(rng, max(len(layer_names), 1))
                    if rng is not None else [None] * len(layer_names))
            rng_map = dict(zip(layer_names, rngs))
            for node in self._order:
                xs = [acts[i] for i in node.inputs]
                if node.kind == "vertex":
                    acts[node.name] = node.vertex.apply(xs)
                    ms = [act_masks.get(i) for i in node.inputs]
                    act_masks[node.name] = next((m for m in ms if m is not None), None)
                else:
                    layer = self.layers[node.name]
                    mask = act_masks.get(node.inputs[0])
                    if (rnn_states is not None
                            and hasattr(layer, "apply_with_state")):
                        x0 = layer._maybe_dropout(xs[0], train=train,
                                                  rng=rng_map[node.name])
                        y, last = layer.apply_with_state(
                            params[node.name], x0, mask=mask,
                            initial=rnn_states.get(node.name))
                        acts[node.name] = y
                        act_masks[node.name] = mask
                        new_state[node.name] = net_state[node.name]
                        new_rnn[node.name] = last
                        continue
                    if new_rnn is not None:
                        new_rnn[node.name] = None
                    if hasattr(layer, "apply_multi"):
                        # parameterized multi-input node (AttentionVertex
                        # role): gets ALL wired inputs; the mask that
                        # matters is the KEYS input's (the last wired
                        # input) — it gates which positions are attended
                        kmask = act_masks.get(node.inputs[-1]) \
                            if len(node.inputs) > 1 else mask
                        y, st, m2 = layer.apply_multi(
                            params[node.name], xs, net_state[node.name],
                            train=train, rng=rng_map[node.name], mask=kmask)
                    else:
                        x = xs[0]
                        if getattr(node, "_flatten_input", False):
                            if x.ndim == 4:  # NHWC → reference C-major flat
                                x = x.transpose(0, 3, 1, 2).reshape(x.shape[0], -1)
                            elif x.ndim == 5:  # NDHWC → C-major flat
                                x = x.transpose(0, 4, 1, 2, 3).reshape(x.shape[0], -1)
                        y, st, m2 = layer.apply(
                            params[node.name], x, net_state[node.name],
                            train=train, rng=rng_map[node.name], mask=mask)
                    acts[node.name] = y
                    act_masks[node.name] = m2
                    new_state[node.name] = st
            if DT.needs_cast(self.conf.dtype):
                for o in self.conf.network_outputs:  # loss/eval math stays f32
                    acts[o] = DT.cast_floats(acts[o], jnp.float32)
        if new_rnn is not None:
            return acts, new_state, new_rnn
        return acts, new_state

    def output(self, *inputs, masks=None) -> List[np.ndarray]:
        """graph.output(inputs...) — list of output-node activations."""
        feed = {n: jnp.asarray(x) for n, x in zip(self.conf.network_inputs, inputs)}
        fn = self._jit_cache.get("output")
        if fn is None:
            @jax.jit
            def fn(params, net_state, feed, masks):
                acts, _ = self._forward(params, net_state, feed, masks,
                                        train=False, rng=None)
                return [acts[o] for o in self.conf.network_outputs]

            self._jit_cache["output"] = fn
        outs = fn(self.params, self.net_state, feed,
                  None if masks is None else {k: jnp.asarray(v) for k, v in masks.items()})
        return [np.asarray(o) for o in outs]

    def output_single(self, x, masks=None) -> np.ndarray:
        return self.output(x, masks=masks)[0]

    # ------------------------------------------------------------- train step
    def _losses(self, acts, labels: Dict[str, Any], lmasks):
        total = jnp.zeros(())
        for name in self._output_layers:
            node = self._node(name)
            if hasattr(node.layer, "loss_fn"):
                loss_fn = node.layer.loss_fn()  # conf-bound hyperparams (YOLO2)
            else:
                loss_fn = get_loss(node.layer.loss)
            lm = None if lmasks is None else lmasks.get(name)
            total = total + loss_fn(acts[name], labels[name], lm)
        return total

    def _apply_updates(self, params, grads, opt_state, step):
        """Shared update tail (regularization-into-grad, updater math) for
        the standard and tBPTT step functions."""
        conf = self.conf
        layer_names = [n.name for n in self._order if n.kind == "layer"]
        updaters = {name: conf.layer_updater(self.layers[name].lc)
                    for name in layer_names}
        updated = apply_layer_updates(
            conf,
            ((params[n], grads[n], opt_state[n], updaters[n],
              self.layers[n].lc) for n in layer_names),
            step, self._normalize_gradient)
        new_params = {n: p for n, (p, _) in zip(layer_names, updated)}
        new_opt = {n: s for n, (_, s) in zip(layer_names, updated)}
        return new_params, new_opt

    def _reg_penalty(self, params):
        layer_names = [n.name for n in self._order if n.kind == "layer"]
        return reg_penalty(
            self.conf, ((params[n], self.layers[n].lc) for n in layer_names))

    def _make_train_step(self):
        def train_step(params, opt_state, net_state, step, key, feeds, labels,
                       fmasks, lmasks):
            def loss_of(p):
                acts, new_state = self._forward(p, net_state, feeds, fmasks,
                                                train=True, rng=key)
                return (self._losses(acts, labels, lmasks)
                        + aux_losses(new_state), new_state)

            (loss, new_net_state), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(params, grads, opt_state, step)
            return (new_params, new_opt, new_net_state,
                    loss + self._reg_penalty(params))

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    _normalize_gradient = None  # assigned below (shared with MultiLayerNetwork)

    # ------------------------------------------------------ stateful RNN API
    def rnn_time_step(self, *inputs, masks=None):
        """Stateful streaming inference (ComputationGraph.rnnTimeStep):
        recurrent node states carry across calls in ``self._rnn_states``.
        Inputs: (N, T, F) per network input — or (N, F) for one step.
        Returns the network outputs (list, or the single array)."""
        squeeze = False
        feeds = {}
        for name, x in zip(self.conf.network_inputs, inputs):
            x = np.asarray(x)
            if x.ndim == 2:
                x = x[:, None, :]
                squeeze = True
            feeds[name] = jnp.asarray(x)
        batch = next(iter(feeds.values())).shape[0]
        if getattr(self, "_rnn_states", None) is None:
            self._rnn_states = self._zero_rnn_states(batch)
        fn = self._jit_cache.get("rnn_time_step")
        if fn is None:
            @jax.jit
            def fn(params, net_state, rnn_states, feeds, masks):
                acts, _, new_rnn = self._forward(
                    params, net_state, feeds, masks, train=False, rng=None,
                    rnn_states=rnn_states)
                return [acts[o] for o in self.conf.network_outputs], new_rnn

            self._jit_cache["rnn_time_step"] = fn
        outs, self._rnn_states = fn(self.params, self.net_state,
                                    self._rnn_states, feeds, masks)
        outs = [np.asarray(o) for o in outs]
        if squeeze:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self) -> None:
        self._rnn_states = None

    def _zero_rnn_states(self, batch: int, dtype=np.float32):
        from deeplearning4j_tpu.nn.layers import BidirectionalImpl

        states: Dict[str, Any] = {}
        for name, layer in self.layers.items():
            if isinstance(layer, BidirectionalImpl):
                raise ValueError(
                    "stateful RNN state (rnn_time_step / tBPTT) is not "
                    "supported with Bidirectional layers")
            states[name] = (layer.zero_state(batch, dtype)
                            if hasattr(layer, "zero_state") else None)
        return states

    def _make_train_step_tbptt(self):
        """Truncated-BPTT step (doTruncatedBPTT analog): RNN state enters as
        an input and leaves as an output — gradients truncate at the segment
        boundary (see MultiLayerNetwork._make_train_step_tbptt)."""
        def train_step(params, opt_state, net_state, rnn_states, step, key,
                       feeds, labels, fmasks, lmasks):
            def loss_of(p):
                acts, new_state, new_rnn = self._forward(
                    p, net_state, feeds, fmasks, train=True, rng=key,
                    rnn_states=rnn_states)
                return self._losses(acts, labels, lmasks), (new_state, new_rnn)

            (loss, (new_net_state, new_rnn)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(params, grads, opt_state, step)
            return (new_params, new_opt, new_net_state, new_rnn,
                    loss + self._reg_penalty(params))

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def fit_tbptt(self, features, labels, masks=None, lmasks=None) -> float:
        """One truncated-BPTT pass over a time-series batch: slices the time
        axis into ``conf.tbptt_fwd_length`` segments, carrying RNN state
        (ComputationGraph.doTruncatedBPTT). Single- or multi-input graphs:
        pass arrays or name-keyed dicts of (N, T, F) features / (N, T, C)
        labels."""
        fwd = self.conf.tbptt_fwd_length
        if fwd <= 0:
            raise ValueError("set tbptt lengths on the configuration first")
        if not isinstance(features, dict):
            features = {self.conf.network_inputs[0]: features}
        if not isinstance(labels, dict):
            labels = {self.conf.network_outputs[0]: labels}
        if masks is not None and not isinstance(masks, dict):
            masks = {self.conf.network_inputs[0]: masks}
        if lmasks is not None and not isinstance(lmasks, dict):
            lmasks = {self.conf.network_outputs[0]: lmasks}
        for k, v in labels.items():
            if np.asarray(v).ndim < 3:
                raise ValueError(
                    "tBPTT requires 3-D time-series labels (N, T, C); got "
                    f"shape {np.shape(v)} for output '{k}'")
        step_fn = self._jit_cache.get("train_step_tbptt")
        if step_fn is None:
            step_fn = self._make_train_step_tbptt()
            self._jit_cache["train_step_tbptt"] = step_fn
        T = next(iter(features.values())).shape[1]
        batch = next(iter(features.values())).shape[0]
        rnn_states = self._zero_rnn_states(batch)
        segments = list(range(0, T, fwd))
        loss = 0.0
        for i, t0 in enumerate(segments):
            t1 = min(t0 + fwd, T)
            seg_f = {k: jnp.asarray(np.asarray(v)[:, t0:t1])
                     for k, v in features.items()}
            seg_y = {k: jnp.asarray(np.asarray(v)[:, t0:t1])
                     for k, v in labels.items()}
            seg_fm = (None if masks is None else
                      {k: jnp.asarray(np.asarray(v)[:, t0:t1])
                       for k, v in masks.items()})
            seg_lm = (None if lmasks is None else
                      {k: jnp.asarray(np.asarray(v)[:, t0:t1])
                       for k, v in lmasks.items()})
            self._key, sub = jax.random.split(self._key)
            (self.params, self.opt_state, self.net_state, rnn_states,
             loss) = step_fn(self.params, self.opt_state, self.net_state,
                             rnn_states,
                             jnp.asarray(self.iteration_count, jnp.int32),
                             sub, seg_f, seg_y, seg_fm, seg_lm)
            self._score = loss
            if i < len(segments) - 1:
                self.iteration_count += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.epoch_count, loss)
        self.iteration_count += 1
        return float(loss)

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32) -> None:
        """fit over DataSet/iterator. Single-input single-output DataSets map
        features -> first input, labels -> first output (MultiDataSet support:
        pass dicts via fit_multi)."""
        if labels is not None:
            data = ListDataSetIterator(DataSet(data, labels), batch_size=batch_size)
        elif isinstance(data, DataSet):
            data = ListDataSetIterator(data, batch_size=batch_size)
        tbptt = (self.conf.backprop_type == "tbptt"
                 and self.conf.tbptt_fwd_length > 0)
        if tbptt:
            # truncated-BPTT dispatch (doTruncatedBPTT), as in
            # MultiLayerNetwork.fit — NOT silent full-sequence BPTT
            for _ in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self)
                skip = self.batch_in_epoch
                for bi, ds in enumerate(data):
                    if bi < skip:
                        continue
                    faults.maybe_fail("preemption")
                    if faults.preemption_requested():
                        notify_preemption(self, self.listeners)
                        return
                    self.last_batch_size = ds.num_examples()
                    # checkpoint saves must not land mid-batch: a segment
                    # snapshot (params mid-batch, stale data cursor, live
                    # RNN carry the payload does not include) could never
                    # resume exactly. Listeners that declare
                    # ``defers_mid_tbptt`` skip themselves per segment and
                    # get ONE batch-boundary call after the cursor update;
                    # score/perf listeners keep their per-segment firing.
                    self._tbptt_mid_batch = True
                    try:
                        loss = self.fit_tbptt(ds.features, ds.labels,
                                              masks=ds.features_mask,
                                              lmasks=ds.labels_mask)
                    finally:
                        self._tbptt_mid_batch = False
                    self.batch_in_epoch = bi + 1
                    for lst in self.listeners:
                        if getattr(lst, "defers_mid_tbptt", False):
                            lst.iteration_done(self, self.iteration_count,
                                               self.epoch_count, loss)
                self.batch_in_epoch = 0
                self.epoch_count += 1
                for lst in self.listeners:
                    lst.on_epoch_end(self)
            notify_fit_done(self, self.listeners)
            return
        step_fn = self._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = self._make_train_step()
            self._jit_cache["train_step"] = step_fn
        in_name = self.conf.network_inputs[0]
        out_name = self.conf.network_outputs[0]
        _m = observe.metrics()
        _steps_c = _m.counter("dl4j_tpu_train_steps_total", model="graph")
        _ex_c = _m.counter("dl4j_tpu_train_examples_total", model="graph")
        _xfer_c = _m.counter("dl4j_tpu_host_to_device_transfers_total",
                             model="graph")
        _step_h = _m.histogram("dl4j_tpu_train_step_seconds", model="graph")
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
                # preemption (docs/ROBUSTNESS.md): injected fault = HARD
                # kill (supervisor restores+resumes); flag = SOFT SIGTERM
                # path (final snapshot, clean exit)
                faults.maybe_fail("preemption")
                if faults.preemption_requested():
                    notify_preemption(self, self.listeners)
                    return
                self.last_batch_size = ds.num_examples()
                observe.note_jit_signature(
                    step_fn, graph="graph", key="train_step",
                    signature=observe.signature_of(
                        x=ds.features, y=ds.labels, fm=ds.features_mask,
                        lm=ds.labels_mask))
                self._key, sub = jax.random.split(self._key)
                feeds = {in_name: jnp.asarray(ds.features)}
                labs = {out_name: jnp.asarray(ds.labels)}
                fmasks = (None if ds.features_mask is None
                          else {in_name: jnp.asarray(ds.features_mask)})
                lmasks = (None if ds.labels_mask is None
                          else {out_name: jnp.asarray(ds.labels_mask)})
                self.params, self.opt_state, self.net_state, loss = step_fn(
                    self.params, self.opt_state, self.net_state,
                    jnp.asarray(self.iteration_count, jnp.int32), sub,
                    feeds, labs, fmasks, lmasks)
                self._score = loss
                self.iteration_count += 1
                self.batch_in_epoch = bi + 1  # cursor BEFORE listeners save
                now = time.perf_counter()
                _step_h.observe(now - t_prev)
                t_prev = now
                n_steps += 1
                _steps_c.inc()
                _ex_c.inc(ds.num_examples())
                _xfer_c.inc(2 + (fmasks is not None) + (lmasks is not None))
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count, self.epoch_count, loss)
            self.batch_in_epoch = 0
            self.epoch_count += 1
            observe.log_event("train_epoch", model="graph",
                              epoch=self.epoch_count, steps=n_steps)
            for lst in self.listeners:
                lst.on_epoch_end(self)
        notify_fit_done(self, self.listeners)

    def fit_multi(self, inputs, labels) -> float:
        """One training step with multiple inputs/outputs (the
        ComputationGraph.fit(MultiDataSet) role). ``inputs``/``labels``:
        lists aligned with network_inputs/network_outputs, or name dicts.
        Returns the step loss."""
        if not isinstance(inputs, dict):
            inputs = dict(zip(self.conf.network_inputs, inputs))
        if not isinstance(labels, dict):
            labels = dict(zip(self.conf.network_outputs, labels))
        step_fn = self._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = self._make_train_step()
            self._jit_cache["train_step"] = step_fn
        self._key, sub = jax.random.split(self._key)
        feeds = {k: jnp.asarray(v) for k, v in inputs.items()}
        labs = {k: jnp.asarray(v) for k, v in labels.items()}
        self.last_batch_size = next(iter(feeds.values())).shape[0]
        self.params, self.opt_state, self.net_state, loss = step_fn(
            self.params, self.opt_state, self.net_state,
            jnp.asarray(self.iteration_count, jnp.int32), sub,
            feeds, labs, None, None)
        self._score = loss
        self.iteration_count += 1
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration_count, self.epoch_count,
                               loss)
        return float(loss)

    def fit_scanned(self, features, labels, steps: Optional[int] = None) -> np.ndarray:
        """Many fused train steps in ONE XLA call — lax.scan over the train
        step with donated carry (see MultiLayerNetwork.fit_scanned; same two
        modes). ``features``/``labels``: single-input/-output arrays, or
        dicts keyed by input/output name for multi-IO graphs."""
        import functools

        step_fn = self._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = self._make_train_step()
            self._jit_cache["train_step"] = step_fn
        if not isinstance(features, dict):
            features = {self.conf.network_inputs[0]: features}
        if not isinstance(labels, dict):
            labels = {self.conf.network_outputs[0]: labels}
        per_step_data = steps is None
        shape = np.shape(next(iter(features.values())))
        n_steps = int(shape[0]) if per_step_data else int(steps)
        self.last_batch_size = int(shape[1] if per_step_data else shape[0])

        cache_key = ("fit_scanned", per_step_data, n_steps)
        many = self._jit_cache.get(cache_key)
        if many is None:
            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def many(params, opt_state, net_state, start, key, feeds, labs):
                def body(carry, it):
                    p, o, s = carry
                    if per_step_data:
                        i, f, y = it
                    else:
                        i, f, y = it, feeds, labs
                    p, o, s, loss = step_fn(p, o, s, i, jax.random.fold_in(key, i),
                                            f, y, None, None)
                    return (p, o, s), loss
                idx = start + jnp.arange(n_steps, dtype=jnp.int32)
                sc = (idx, feeds, labs) if per_step_data else idx
                (p, o, s), losses = jax.lax.scan(body, (params, opt_state, net_state), sc)
                return p, o, s, losses

            self._jit_cache[cache_key] = many
        with observe.scanned_call(
                "graph", n_steps, n_steps * self.last_batch_size) as call:
            with call.dispatch():
                feeds = {k: jnp.asarray(v) for k, v in features.items()}
                labs = {k: jnp.asarray(v) for k, v in labels.items()}
                self._key, sub = jax.random.split(self._key)
                self.params, self.opt_state, self.net_state, losses = many(
                    self.params, self.opt_state, self.net_state,
                    jnp.asarray(self.iteration_count, jnp.int32), sub,
                    feeds, labs)
                self._score = losses[-1]
            start = self.iteration_count
            self.iteration_count += n_steps
            with call.read():
                losses = np.asarray(losses)
        # fire listeners after the fused chunk (per-step losses; params only
        # current as of chunk end) — the fast path no longer skips them.
        # Iteration-major order so multi-listener interleaving matches fit()
        for k in range(n_steps):
            for lst in self.listeners:
                lst.iteration_done(self, start + k + 1, self.epoch_count,
                                   float(losses[k]))
        return losses

    def score(self) -> float:
        return float(getattr(self, "_score", float("nan")))

    def evaluate(self, iterator, evaluation=None) -> Evaluation:
        e = evaluation if evaluation is not None else Evaluation()
        if isinstance(iterator, DataSet):
            iterator = ListDataSetIterator(iterator, batch_size=256)
        in_name = self.conf.network_inputs[0]
        for ds in iterator:
            masks = (None if ds.features_mask is None
                     else {in_name: ds.features_mask})
            out = self.output_single(ds.features, masks=masks)
            e.eval(ds.labels, out, ds.labels_mask)
        return e

    # ---------------------------------------------------- flat params / serde
    def params_flat(self) -> np.ndarray:
        leaves = []
        for name in sorted(self.params):
            leaves.extend(_sorted_leaves(self.params[name]))
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_params_flat(self, flat: np.ndarray) -> None:
        flat = np.asarray(flat)
        offset = 0
        new_params = {}
        for name in sorted(self.params):
            new_p, offset = _unflatten_like(self.params[name], flat, offset)
            new_params[name] = new_p
        if offset != flat.size:
            raise ValueError(f"param vector length {flat.size} != model size {offset}")
        self.params = jax.tree.map(jnp.asarray, new_params)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for p in self.params.values() for l in jax.tree.leaves(p))


# share the gradient-normalization logic with MultiLayerNetwork
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork as _MLN  # noqa: E402

ComputationGraph._normalize_gradient = _MLN._normalize_gradient


def save_graph(net: ComputationGraph, path: str, save_updater: bool = True) -> None:
    """ModelSerializer.writeModel for ComputationGraph."""
    import zipfile

    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("configuration.json", net.conf.to_json())
        z.writestr("coefficients.bin", net.params_flat().astype(np.float32).tobytes())
        meta = {"iteration_count": net.iteration_count, "epoch_count": net.epoch_count,
                "model_type": "ComputationGraph"}
        z.writestr("meta.json", json.dumps(meta))
        if save_updater and net.opt_state is not None:
            leaves = []
            for name in sorted(net.opt_state):
                leaves.extend(_sorted_leaves(net.opt_state[name]))
            blob = (np.concatenate([np.asarray(l).reshape(-1) for l in leaves])
                    if leaves else np.zeros((0,), np.float32))
            z.writestr("updaterState.bin", blob.astype(np.float32).tobytes())


def restore_graph(path: str, load_updater: bool = True) -> ComputationGraph:
    import zipfile

    with zipfile.ZipFile(path, "r") as z:
        conf = ComputationGraphConfiguration.from_json(z.read("configuration.json").decode())
        net = ComputationGraph(conf).init()
        net.set_params_flat(np.frombuffer(z.read("coefficients.bin"), np.float32))
        if "meta.json" in z.namelist():
            meta = json.loads(z.read("meta.json").decode())
            net.iteration_count = meta.get("iteration_count", 0)
            net.epoch_count = meta.get("epoch_count", 0)
        if load_updater and "updaterState.bin" in z.namelist():
            flat = np.frombuffer(z.read("updaterState.bin"), np.float32)
            offset = 0
            new_states = {}
            for name in sorted(net.opt_state):
                ns, offset = _unflatten_like(net.opt_state[name], flat, offset)
                new_states[name] = ns
            net.opt_state = jax.tree.map(jnp.asarray, new_states)
    return net
