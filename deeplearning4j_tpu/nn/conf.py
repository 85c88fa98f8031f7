"""Declarative layer/network configuration with JSON round-trip.

Reference parity:
  * org/deeplearning4j/nn/conf/NeuralNetConfiguration.java (builder),
    MultiLayerConfiguration.java, conf/layers/* (DenseLayer, ConvolutionLayer,
    SubsamplingLayer, BatchNormalization, LSTM, EmbeddingLayer, OutputLayer,
    ...), conf/inputs/InputType.java (shape inference between layers),
    conf/preprocessor/* (shape adapters).
  * Jackson-polymorphic JSON serialization — the property that makes
    ModelSerializer zips self-describing — is reproduced with an "@type"
    discriminator and dataclass round-trip.

TPU-native realization: configs are frozen dataclasses; ``build()`` produces a
``MultiLayerConfiguration`` whose layers know how to (a) infer their output
InputType, (b) initialize a param pytree leaf-dict, and (c) apply as a pure
function (see layers.py). The runtime model (multilayer.py) compiles the whole
stack into one XLA program.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from deeplearning4j_tpu.nn.updater import Updater, Adam, get_updater

# ---------------------------------------------------------------------------
# InputType — shape inference tokens (conf/inputs/InputType.java)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputType:
    """Shape token flowing between layer configs at build time.

    kind: 'feedforward' (size,), 'recurrent' (size, timesteps),
    'convolutional' (height, width, channels — stored NHWC internally per
    SURVEY §8.3 layout policy; the NCHW reference order is accepted at the API
    edge), 'convolutionalflat'.
    """

    kind: str
    size: int = 0
    height: int = 0
    width: int = 0
    channels: int = 0
    depth: int = 0  # convolutional3d only
    timesteps: int = -1  # -1: variable

    @staticmethod
    def feed_forward(size: int) -> "InputType":
        return InputType("feedforward", size=size)

    @staticmethod
    def recurrent(size: int, timesteps: int = -1) -> "InputType":
        return InputType("recurrent", size=size, timesteps=timesteps)

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> "InputType":
        return InputType("convolutional", height=height, width=width, channels=channels)

    @staticmethod
    def convolutional3d(depth: int, height: int, width: int,
                        channels: int) -> "InputType":
        """NDHWC volumetric input (InputType.InputTypeConvolutional3D)."""
        return InputType("convolutional3d", depth=depth, height=height,
                         width=width, channels=channels)

    @staticmethod
    def convolutional_flat(height: int, width: int, channels: int) -> "InputType":
        return InputType(
            "convolutionalflat",
            size=height * width * channels,
            height=height,
            width=width,
            channels=channels,
        )

    def flat_size(self) -> int:
        if self.kind in ("feedforward", "convolutionalflat", "recurrent"):
            return self.size if self.size else self.height * self.width * self.channels
        if self.kind == "convolutional3d":
            return self.depth * self.height * self.width * self.channels
        return self.height * self.width * self.channels

    def to_dict(self):
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d):
        return InputType(**d)


# ---------------------------------------------------------------------------
# Layer configs
# ---------------------------------------------------------------------------


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def dl4j_drop_out(retain_prob: float) -> float:
    """Convert the reference's ``dropOut(x)`` retain-probability argument
    (conf/layers/Layer.java — x = probability an activation is KEPT) to this
    framework's ``dropout`` drop rate. dropOut(0.8) → dropout=0.2."""
    if retain_prob == 0.0:
        return 0.0  # reference sentinel: dropOut(0.0) means dropout disabled
    if not 0.0 < retain_prob <= 1.0:
        raise ValueError(f"retain probability must be in [0, 1], got {retain_prob}")
    return 1.0 - retain_prob


@dataclasses.dataclass(frozen=True)
class LayerConf:
    """Base layer config (conf/layers/Layer.java analog).

    Per-layer overrides of the net-wide defaults (updater/lr/regularization/
    weight init) mirror the reference's layer-level overrides.
    """

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    weight_decay: Optional[float] = None
    # DROP RATE (fraction zeroed), NOT the reference's dropOut(x) retain
    # probability. Porting a DL4J config? Use dl4j_drop_out(retain_prob) to
    # convert — dropOut(0.8) in the reference means keep-80%, i.e. dropout=0.2.
    dropout: Optional[float] = None
    updater: Optional[Any] = None

    # --- overridden by subclasses ---
    def output_type(self, itype: InputType) -> InputType:
        return itype

    def has_params(self) -> bool:
        return False

    # JSON
    def to_dict(self) -> Dict[str, Any]:
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, Updater):
                v = {"__updater__": v.to_dict()}
            d[f.name] = v
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "LayerConf":
        def tuplify(v):
            return tuple(tuplify(x) for x in v) if isinstance(v, list) else v

        d = dict(d)
        cls = LAYER_TYPES[d.pop("@type")]
        for k, v in list(d.items()):
            if isinstance(v, dict) and "__updater__" in v:
                d[k] = Updater.from_dict(v["__updater__"])
            elif isinstance(v, list):
                d[k] = tuplify(v)
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class DenseLayer(LayerConf):
    """conf/layers/DenseLayer.java: fully connected, W (nIn,nOut) + b."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = True

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class OutputLayer(DenseLayer):
    """conf/layers/OutputLayer.java: dense + loss function."""

    loss: str = "mcxent"


@dataclasses.dataclass(frozen=True)
class LossLayer(LayerConf):
    """conf/layers/LossLayer.java: loss without params (identity transform)."""

    loss: str = "mcxent"


@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(LayerConf):
    """conf/layers/EmbeddingLayer.java: int ids -> embedding rows."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = False

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(LayerConf):
    """conf/layers/EmbeddingSequenceLayer.java: id sequence -> vec sequence."""

    n_in: int = 0
    n_out: int = 0
    input_length: int = -1

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, self.input_length)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(LayerConf):
    """conf/layers/ConvolutionLayer.java.

    NCHW at the API edge (reference default, `hasBias`, `convolutionMode`);
    NHWC internally (SURVEY §8.3). kernel/stride/dilation are (h, w) pairs.
    convolution_mode: 'truncate' (reference Truncate ≙ VALID-with-truncation)
    or 'same'.
    """

    n_in: int = 0
    n_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"
    has_bias: bool = True
    # TPU stem optimization: lower a 7x7/stride-2/'same' conv as a 4x4/stride-1
    # conv over a 2x2 space-to-depth input (MLPerf ResNet trick). Mathematically
    # exact — the canonical (7,7,C,F) kernel is kept in params and zero-padded/
    # regrouped at apply time, so checkpoints and gradients are identical; only
    # the XLA lowering changes (C=3 convs waste the MXU's 128-wide lanes).
    s2d_stem: bool = False

    def output_type(self, itype):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        ph, pw = _pair(self.padding)
        ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self.convolution_mode == "same":
            oh = -(-itype.height // sh)
            ow = -(-itype.width // sw)
        else:
            oh = (itype.height + 2 * ph - ekh) // sh + 1
            ow = (itype.width + 2 * pw - ekw) // sw + 1
        return InputType.convolutional(oh, ow, self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Deconvolution2D(ConvolutionLayer):
    """conf/layers/Deconvolution2D.java: transposed convolution."""

    def output_type(self, itype):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            oh, ow = itype.height * sh, itype.width * sw
        else:
            oh = sh * (itype.height - 1) + kh - 2 * ph
            ow = sw * (itype.width - 1) + kw - 2 * pw
        return InputType.convolutional(oh, ow, self.n_out)


@dataclasses.dataclass(frozen=True)
class DepthwiseConvolution2D(ConvolutionLayer):
    """conf/layers/DepthwiseConvolution2D.java (depth_multiplier folded into n_out)."""

    depth_multiplier: int = 1

    def output_type(self, itype):
        base = super().output_type(itype)
        return InputType.convolutional(base.height, base.width, itype.channels * self.depth_multiplier)


@dataclasses.dataclass(frozen=True)
class SeparableConvolution2D(ConvolutionLayer):
    """conf/layers/SeparableConvolution2D.java."""

    depth_multiplier: int = 1


@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(LayerConf):
    """conf/layers/SubsamplingLayer.java: pooling (MAX/AVG/PNORM)."""

    pooling_type: str = "max"  # max | avg | pnorm
    kernel: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def output_type(self, itype):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            oh = -(-itype.height // sh)
            ow = -(-itype.width // sw)
        else:
            oh = (itype.height + 2 * ph - kh) // sh + 1
            ow = (itype.width + 2 * pw - kw) // sw + 1
        return InputType.convolutional(oh, ow, itype.channels)


@dataclasses.dataclass(frozen=True)
class Upsampling2D(LayerConf):
    """conf/layers/Upsampling2D.java."""

    size: Tuple[int, int] = (2, 2)

    def output_type(self, itype):
        sh, sw = _pair(self.size)
        return InputType.convolutional(itype.height * sh, itype.width * sw, itype.channels)


@dataclasses.dataclass(frozen=True)
class GlobalPoolingLayer(LayerConf):
    """conf/layers/GlobalPoolingLayer.java: conv/recurrent -> feedforward."""

    pooling_type: str = "avg"  # avg | max | sum | pnorm

    def output_type(self, itype):
        if itype.kind == "recurrent":
            return InputType.feed_forward(itype.size)
        return InputType.feed_forward(itype.channels)


@dataclasses.dataclass(frozen=True)
class BatchNormalization(LayerConf):
    """conf/layers/BatchNormalization.java: gamma/beta + running stats."""

    n_out: int = 0  # inferred if 0
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False

    def output_type(self, itype):
        return itype

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class DuelingQLayer(LayerConf):
    """Dueling-DQN head (reference RL4J QLearning dueling configuration):
    value stream V(s) (scalar) + advantage stream A(s,·), combined with the
    standard identifiable aggregation Q = V + A − mean(A)."""

    n_in: int = 0
    n_actions: int = 0

    def output_type(self, itype):
        return InputType.feed_forward(self.n_actions)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class MoELayer(LayerConf):
    """Mixture-of-Experts FFN layer (GShard/Switch recipe) as a standard
    LayerConf — usable in MultiLayerNetwork/ComputationGraph and composing
    with ParallelWrapper(mesh={'data':…, 'expert':…}) + ``moe_ep_rules()``:
    the dispatch/combine einsums are written dense so GSPMD partitions the
    expert axis and inserts the all-to-alls (no hand shard_map).

    top_k=1 is Switch routing, top_k=2 the GShard default. Assignments past
    capacity C = ceil(cf·S·k/E) are dropped; a token whose EVERY assignment
    is dropped passes through as identity (never zeros). The load-balance
    aux loss rides the layer STATE under ``_aux_loss`` (summed into the
    training loss by the step functions); ``_dropped_frac`` reports the
    fraction of token→expert assignments dropped at capacity — surfaced to
    listeners/UI as a routing-health diagnostic.

    Exceeds-reference axis (SURVEY §6.7): the reference has no MoE; recipe
    per the public GShard/Switch papers.
    """

    n_in: int = 0
    d_hidden: int = 0
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 1e-2

    def output_type(self, itype):
        return itype

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(LayerConf):
    """conf/layers/LocalResponseNormalization.java."""

    n: int = 5
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75


@dataclasses.dataclass(frozen=True)
class ActivationLayer(LayerConf):
    """conf/layers/ActivationLayer.java: standalone activation."""


@dataclasses.dataclass(frozen=True)
class DropoutLayer(LayerConf):
    """conf/layers/DropoutLayer.java: standalone dropout.

    ``mode`` selects the IDropout variant (conf/dropout/*.java):
    "elementwise" (Dropout), "spatial" (SpatialDropout — drops whole
    feature maps along the trailing channel axis), "alpha"
    (AlphaDropout — SELU-preserving), "gaussian" (GaussianDropout —
    multiplicative N(1, rate/(1-rate)) noise).
    """

    rate: float = 0.5
    mode: str = "elementwise"


@dataclasses.dataclass(frozen=True)
class LSTM(LayerConf):
    """conf/layers/LSTM.java: scan-based LSTM over the time axis.

    Gate order and math follow the reference LSTMHelpers.java
    (input/forget/output/cell-gate with optional forget-gate bias init).
    """

    n_in: int = 0
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class GravesLSTM(LSTM):
    """conf/layers/GravesLSTM.java (legacy peephole variant — math matches
    plain LSTM here; peepholes omitted, documented divergence)."""


@dataclasses.dataclass(frozen=True)
class GRU(LayerConf):
    """GRU recurrent layer over the catalog's ``gru_cell`` declarable op
    (libnd4j gruCell.cpp — the reference exposes the CELL op but never grew
    a layer around it; this closes that gap). Gate order r, z, n with
    separate input/recurrent biases (the Keras reset_after=True / PyTorch
    convention, so imported weights drop straight in)."""

    n_in: int = 0
    n_out: int = 0

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class SimpleRnn(LayerConf):
    """conf/layers/recurrent/SimpleRnn.java."""

    n_in: int = 0
    n_out: int = 0

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Bidirectional(LayerConf):
    """conf/layers/recurrent/Bidirectional.java: wraps an RNN layer config.

    mode: CONCAT | ADD | MUL | AVERAGE (reference Bidirectional.Mode).
    """

    fwd: Optional[Dict[str, Any]] = None  # serialized inner LayerConf
    mode: str = "concat"

    def inner(self) -> LayerConf:
        return LayerConf.from_dict(dict(self.fwd))

    def output_type(self, itype):
        out = self.inner().output_type(itype)
        if self.mode == "concat":
            return InputType.recurrent(out.size * 2, out.timesteps)
        return out

    def has_params(self):
        return True

    @staticmethod
    def wrap(inner: LayerConf, mode: str = "concat", name=None) -> "Bidirectional":
        return Bidirectional(fwd=inner.to_dict(), mode=mode, name=name)


@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(LayerConf):
    """conf/layers/RnnOutputLayer.java: per-timestep dense + loss."""

    n_in: int = 0
    n_out: int = 0
    loss: str = "mcxent"
    has_bias: bool = True

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class LastTimeStep(LayerConf):
    """conf/layers/recurrent/LastTimeStep.java: wraps an RNN, emits last step
    (mask-aware)."""

    fwd: Optional[Dict[str, Any]] = None
    mode: str = "last"

    def inner(self) -> LayerConf:
        return LayerConf.from_dict(dict(self.fwd))

    def output_type(self, itype):
        out = self.inner().output_type(itype)
        return InputType.feed_forward(out.size)

    def has_params(self):
        return True

    @staticmethod
    def wrap(inner: LayerConf, name=None) -> "LastTimeStep":
        return LastTimeStep(fwd=inner.to_dict(), name=name)


@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(LayerConf):
    """conf/layers/SelfAttentionLayer.java: MHA over a sequence, Q=K=V=input."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    project_input: bool = True

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class LearnedSelfAttentionLayer(LayerConf):
    """conf/layers/LearnedSelfAttentionLayer.java: a fixed set of LEARNED
    query vectors attends over the input sequence — output has n_queries
    timesteps regardless of input length (the reference's fixed-size
    sequence summarizer)."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    n_queries: int = 1

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, self.n_queries)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class RecurrentAttentionLayer(LayerConf):
    """conf/layers/RecurrentAttentionLayer.java: RNN whose step input is
    augmented with single-head attention over the whole input sequence,
    queried by the previous hidden state — out_t = act(Wx·x_t + Wr·attn_t
    + b)."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    activation: str = "tanh"

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class AttentionVertex(LayerConf):
    """conf/graph/AttentionVertex.java: multi-head attention as a GRAPH
    vertex with PARAMS — inputs (queries, keys, values) or (queries,
    keys=values). Registered through GraphBuilder.add_vertex (which routes
    parameterized vertices onto the layer path)."""

    n_out: int = 0
    n_heads: int = 1
    n_in_queries: int = 0
    n_in_keys: int = 0
    n_in_values: int = 0
    # Keras MultiHeadAttention call order is (query, VALUE, key) — set by
    # the importer so 3-input wiring lands on (q, k, v) internally
    keras_order: bool = False
    has_bias: bool = False
    d_out: int = 0  # output projection width when != n_out (keras MHA)

    def output_type(self, itype):
        return InputType.recurrent(self.d_out or self.n_out, itype.timesteps)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Convolution1D(LayerConf):
    """conf/layers/Convolution1DLayer.java: temporal conv over (N, T, C)."""

    n_in: int = 0
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    convolution_mode: str = "same"  # same | valid (truncate)
    dilation: int = 1

    def output_type(self, itype):
        t = itype.timesteps
        if t and t > 0:
            if self.convolution_mode == "same":
                t = -(-t // self.stride)
            else:
                eff = (self.kernel - 1) * self.dilation + 1
                t = (t - eff) // self.stride + 1
        return InputType.recurrent(self.n_out, t)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Convolution3D(LayerConf):
    """conf/layers/Convolution3D.java: volumetric conv over (N, D, H, W, C)
    (NDHWC — the TPU-friendly channels-last 3-D layout)."""

    n_in: int = 0
    n_out: int = 0
    kernel: Tuple[int, int, int] = (3, 3, 3)
    stride: Tuple[int, int, int] = (1, 1, 1)
    convolution_mode: str = "same"

    def output_type(self, itype):
        def out(sz, k, s):
            return -(-sz // s) if self.convolution_mode == "same" \
                else (sz - k) // s + 1

        k, s = self.kernel, self.stride
        return InputType.convolutional3d(
            out(itype.depth, k[0], s[0]), out(itype.height, k[1], s[1]),
            out(itype.width, k[2], s[2]), self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Subsampling3DLayer(LayerConf):
    """conf/layers/Subsampling3DLayer.java: 3-D pooling (NDHWC)."""

    kernel: Tuple[int, int, int] = (2, 2, 2)
    stride: Tuple[int, int, int] = (2, 2, 2)
    pooling_type: str = "max"

    def output_type(self, itype):
        k, s = self.kernel, self.stride
        return InputType.convolutional3d(
            (itype.depth - k[0]) // s[0] + 1,
            (itype.height - k[1]) // s[1] + 1,
            (itype.width - k[2]) // s[2] + 1, itype.channels)


@dataclasses.dataclass(frozen=True)
class LocallyConnected2D(LayerConf):
    """conf/layers/LocallyConnected2D.java: conv topology with UNSHARED
    per-position weights."""

    n_in: int = 0
    n_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    input_size: Tuple[int, int] = (0, 0)  # inferred at build when 0

    def output_type(self, itype):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        return InputType.convolutional(
            (itype.height - kh) // sh + 1, (itype.width - kw) // sw + 1,
            self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class LocallyConnected1D(LayerConf):
    """conf/layers/LocallyConnected1D.java: temporal locally-connected."""

    n_in: int = 0
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    input_size: int = 0

    def output_type(self, itype):
        t = (itype.timesteps - self.kernel) // self.stride + 1 \
            if itype.timesteps and itype.timesteps > 0 else itype.timesteps
        return InputType.recurrent(self.n_out, t)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class PReLULayer(LayerConf):
    """conf/layers/PReLULayer.java: y = max(0,x) + alpha·min(0,x) with a
    LEARNED per-feature alpha."""

    n_in: int = 0  # feature count (last-axis size)

    def output_type(self, itype):
        return itype

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class VariationalAutoencoder(LayerConf):
    """conf/layers/variational/VariationalAutoencoder.java: pretrainable
    VAE layer. Supervised forward emits the latent MEAN (the reference's
    activate() semantics); reconstruction_log_prob / pretrain losses live
    on the impl."""

    n_in: int = 0
    n_out: int = 0  # latent size
    encoder_layer_sizes: Tuple[int, ...] = (256,)
    decoder_layer_sizes: Tuple[int, ...] = (256,)
    activation: str = "leakyrelu"
    reconstruction_distribution: str = "gaussian"  # gaussian | bernoulli

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class ZeroPadding1DLayer(LayerConf):
    """conf/layers/ZeroPadding1DLayer.java: pad the time axis of (N, T, C)."""

    padding: Tuple[int, int] = (1, 1)

    def output_type(self, itype):
        t = itype.timesteps
        p = _pair(self.padding)
        return InputType.recurrent(itype.size, t + p[0] + p[1] if t and t > 0 else t)


@dataclasses.dataclass(frozen=True)
class ZeroPaddingLayer(LayerConf):
    """conf/layers/ZeroPaddingLayer.java: spatial zero-pad, NHWC.
    ``padding`` = (top, bottom, left, right)."""

    padding: Tuple[int, int, int, int] = (1, 1, 1, 1)

    def output_type(self, itype):
        t, b, l, r = self.padding
        return InputType.convolutional(itype.height + t + b,
                                       itype.width + l + r, itype.channels)


@dataclasses.dataclass(frozen=True)
class ZeroPadding3DLayer(LayerConf):
    """conf/layers/ZeroPadding3DLayer.java: NDHWC zero-pad.
    ``padding`` = (d_lo, d_hi, h_lo, h_hi, w_lo, w_hi)."""

    padding: Tuple[int, int, int, int, int, int] = (1, 1, 1, 1, 1, 1)

    def output_type(self, itype):
        p = self.padding
        return InputType.convolutional3d(
            itype.depth + p[0] + p[1], itype.height + p[2] + p[3],
            itype.width + p[4] + p[5], itype.channels)


@dataclasses.dataclass(frozen=True)
class Cropping1D(LayerConf):
    """conf/layers/convolutional/Cropping1D.java: crop the time axis."""

    cropping: Tuple[int, int] = (1, 1)

    def output_type(self, itype):
        t = itype.timesteps
        c = _pair(self.cropping)
        return InputType.recurrent(itype.size, t - c[0] - c[1] if t and t > 0 else t)


@dataclasses.dataclass(frozen=True)
class Cropping2D(LayerConf):
    """conf/layers/convolutional/Cropping2D.java: spatial crop, NHWC.
    ``cropping`` = (top, bottom, left, right)."""

    cropping: Tuple[int, int, int, int] = (1, 1, 1, 1)

    def output_type(self, itype):
        t, b, l, r = self.cropping
        return InputType.convolutional(itype.height - t - b,
                                       itype.width - l - r, itype.channels)


@dataclasses.dataclass(frozen=True)
class Cropping3D(LayerConf):
    """conf/layers/convolutional/Cropping3D.java: NDHWC crop.
    ``cropping`` = (d_lo, d_hi, h_lo, h_hi, w_lo, w_hi)."""

    cropping: Tuple[int, int, int, int, int, int] = (1, 1, 1, 1, 1, 1)

    def output_type(self, itype):
        c = self.cropping
        return InputType.convolutional3d(
            itype.depth - c[0] - c[1], itype.height - c[2] - c[3],
            itype.width - c[4] - c[5], itype.channels)


@dataclasses.dataclass(frozen=True)
class Upsampling1D(LayerConf):
    """conf/layers/Upsampling1D.java: repeat each timestep ``size`` times."""

    size: int = 2

    def output_type(self, itype):
        t = itype.timesteps
        return InputType.recurrent(itype.size, t * self.size if t and t > 0 else t)


@dataclasses.dataclass(frozen=True)
class Upsampling3D(LayerConf):
    """conf/layers/Upsampling3D.java: nearest-neighbour ×size, NDHWC."""

    size: Tuple[int, int, int] = (2, 2, 2)

    def output_type(self, itype):
        s = self.size
        return InputType.convolutional3d(itype.depth * s[0], itype.height * s[1],
                                         itype.width * s[2], itype.channels)


@dataclasses.dataclass(frozen=True)
class Subsampling1DLayer(LayerConf):
    """conf/layers/Subsampling1DLayer.java: temporal pooling over (N, T, C)."""

    kernel: int = 2
    stride: int = 2
    pooling_type: str = "max"  # max | avg
    convolution_mode: str = "valid"

    def output_type(self, itype):
        t = itype.timesteps
        if t and t > 0:
            if self.convolution_mode == "same":
                t = -(-t // self.stride)
            else:
                t = (t - self.kernel) // self.stride + 1
        return InputType.recurrent(itype.size, t)


@dataclasses.dataclass(frozen=True)
class Deconvolution3D(LayerConf):
    """conf/layers/Deconvolution3D.java: transposed volumetric conv, NDHWC."""

    n_in: int = 0
    n_out: int = 0
    kernel: Tuple[int, int, int] = (2, 2, 2)
    stride: Tuple[int, int, int] = (2, 2, 2)
    convolution_mode: str = "valid"

    def output_type(self, itype):
        def out(sz, k, s):
            return sz * s if self.convolution_mode == "same" else (sz - 1) * s + k

        k, s = self.kernel, self.stride
        return InputType.convolutional3d(
            out(itype.depth, k[0], s[0]), out(itype.height, k[1], s[1]),
            out(itype.width, k[2], s[2]), self.n_out)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class CnnLossLayer(LayerConf):
    """conf/layers/CnnLossLayer.java: per-position 2-D loss (segmentation).
    No params; activation applied; labels shaped (N, H, W, C)."""

    loss: str = "mcxent"


@dataclasses.dataclass(frozen=True)
class RnnLossLayer(LayerConf):
    """conf/layers/RnnLossLayer.java: per-timestep loss over (N, T, C)."""

    loss: str = "mcxent"


@dataclasses.dataclass(frozen=True)
class MaskLayer(LayerConf):
    """conf/layers/util/MaskLayer.java: apply the current mask to the
    activations (zero masked timesteps), pass everything else through."""


@dataclasses.dataclass(frozen=True)
class MaskZeroLayer(LayerConf):
    """conf/layers/recurrent/MaskZeroLayer.java: derive a timestep mask from
    the input (steps where ALL features == mask_value are masked) before
    running the wrapped recurrent layer."""

    underlying: Optional[Any] = None  # LayerConf
    mask_value: float = 0.0

    def inner(self) -> "LayerConf":
        u = self.underlying
        return LayerConf.from_dict(u) if isinstance(u, dict) else u

    def output_type(self, itype):
        return self.inner().output_type(itype)

    def has_params(self):
        return self.inner().has_params()

    def to_dict(self):
        d = super().to_dict()
        if isinstance(d.get("underlying"), LayerConf):
            d["underlying"] = d["underlying"].to_dict()
        return d


@dataclasses.dataclass(frozen=True)
class RepeatVector(LayerConf):
    """conf/layers/misc/RepeatVector.java: (N, F) -> (N, n, F)."""

    n: int = 1

    def output_type(self, itype):
        return InputType.recurrent(itype.flat_size(), self.n)


@dataclasses.dataclass(frozen=True)
class ElementWiseMultiplicationLayer(LayerConf):
    """conf/layers/misc/ElementWiseMultiplicationLayer.java:
    out = act(x ⊙ w + b) with a learned per-feature scale."""

    n_in: int = 0
    n_out: int = 0

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out or itype.flat_size())

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class FrozenLayerWithBackprop(LayerConf):
    """conf/layers/misc/FrozenLayerWithBackprop.java: wrapped layer gets NO
    parameter updates but still backprops gradients to earlier layers
    (FrozenLayer, by contrast, also blocks the flow — that variant lives in
    nn/transfer.py as the TransferLearning freeze mechanism)."""

    underlying: Optional[Any] = None

    def inner(self) -> "LayerConf":
        u = self.underlying
        return LayerConf.from_dict(u) if isinstance(u, dict) else u

    def output_type(self, itype):
        return self.inner().output_type(itype)

    def has_params(self):
        return self.inner().has_params()

    def to_dict(self):
        d = super().to_dict()
        if isinstance(d.get("underlying"), LayerConf):
            d["underlying"] = d["underlying"].to_dict()
        return d


@dataclasses.dataclass(frozen=True)
class CenterLossOutputLayer(DenseLayer):
    """conf/layers/CenterLossOutputLayer.java: softmax classification plus
    a decoupled center loss — λ·½‖f − sg(c_y)‖² pulls FEATURES toward their
    class center, α·½‖sg(f) − c_y‖² pulls CENTERS toward the batch features
    (its gradient α(c_y − f̄) is the reference's moving-average center
    update c ← c − α(c − f̄), realized through the optimizer)."""

    loss: str = "mcxent"
    alpha: float = 0.05     # center pull rate (reference `alpha`)
    lambda_: float = 2e-4   # feature-pull weight (reference `lambda`)


@dataclasses.dataclass(frozen=True)
class Yolo2OutputLayer(LayerConf):
    """conf/layers/objdetect/Yolo2OutputLayer.java: YOLOv2 anchor-box output.
    Forward is identity (activations are decoded inside the loss); the loss
    is the multi-part sum-squared objective (models/zoo.py TinyYOLO
    yolo_loss). Labels: (N, H, W, B, 5 + C) matching the prediction grid."""

    anchors: Tuple[Tuple[float, float], ...] = ()
    lambda_coord: float = 5.0
    lambda_noobj: float = 0.5
    loss: str = "yolo2"

    def loss_fn(self):
        """Bind THIS conf's lambdas/anchors into the shared yolo2 loss —
        networks check for a conf-provided loss_fn before get_loss(name)."""
        import functools

        from deeplearning4j_tpu.ops.losses import yolo2

        return functools.partial(
            yolo2, lambda_coord=self.lambda_coord,
            lambda_noobj=self.lambda_noobj,
            anchors=[list(a) for a in self.anchors] or None)

    def to_dict(self):
        d = super().to_dict()
        d["anchors"] = [list(a) for a in self.anchors]
        return d


@dataclasses.dataclass(frozen=True)
class PrimaryCapsules(LayerConf):
    """conf/layers/PrimaryCapsules.java (CapsNet): conv into
    (N, capsules, capsule_dim) with squash nonlinearity."""

    capsules: int = 8          # number of capsule CHANNELS (per spatial pos)
    capsule_dim: int = 8
    kernel: Tuple[int, int] = (9, 9)
    stride: Tuple[int, int] = (2, 2)

    def output_type(self, itype):
        kh, kw = self.kernel
        sh, sw = self.stride
        oh = (itype.height - kh) // sh + 1
        ow = (itype.width - kw) // sw + 1
        return InputType.recurrent(self.capsule_dim, oh * ow * self.capsules)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class CapsuleLayer(LayerConf):
    """conf/layers/CapsuleLayer.java: dynamic-routing capsules.
    Input (N, in_caps, in_dim) -> (N, capsules, capsule_dim)."""

    capsules: int = 10
    capsule_dim: int = 16
    routings: int = 3

    def output_type(self, itype):
        return InputType.recurrent(self.capsule_dim, self.capsules)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class CapsuleStrengthLayer(LayerConf):
    """conf/layers/CapsuleStrengthLayer.java: ‖capsule‖₂ per capsule —
    (N, caps, dim) -> (N, caps)."""

    def output_type(self, itype):
        return InputType.feed_forward(itype.timesteps if itype.timesteps > 0
                                      else itype.size)


# ---------------------------------------------------------------------------
# Preprocessors (conf/preprocessor/*) — shape adapters between layers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputPreProcessor:
    """Base preprocessor. Applied to the activations flowing between layers."""

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d):
        if d is None:
            return None
        d = dict(d)
        return PREPROCESSORS[d.pop("@type")](**d)


@dataclasses.dataclass(frozen=True)
class FeedForwardToCnnPreProcessor(InputPreProcessor):
    """(N, H*W*C) -> (N, H, W, C) [reference: -> NCHW; NHWC internally]."""

    height: int = 0
    width: int = 0
    channels: int = 0


@dataclasses.dataclass(frozen=True)
class CnnToFeedForwardPreProcessor(InputPreProcessor):
    """(N, H, W, C) -> (N, H*W*C); flatten order matches reference NCHW
    flattening (C-major) so exported flat params/activations line up."""

    height: int = 0
    width: int = 0
    channels: int = 0


@dataclasses.dataclass(frozen=True)
class Cnn3DToFeedForwardPreProcessor(InputPreProcessor):
    """(N, D, H, W, C) -> (N, D·H·W·C) (Cnn3DToFeedForwardPreProcessor.java;
    C-major flatten matching the reference NCDHW ordering)."""

    depth: int = 0
    height: int = 0
    width: int = 0
    channels: int = 0


@dataclasses.dataclass(frozen=True)
class RnnToFeedForwardPreProcessor(InputPreProcessor):
    """(N, T, F) -> (N*T, F)."""


@dataclasses.dataclass(frozen=True)
class FeedForwardToRnnPreProcessor(InputPreProcessor):
    """(N*T, F) -> (N, T, F)."""


PREPROCESSORS = {
    c.__name__: c
    for c in [
        FeedForwardToCnnPreProcessor,
        CnnToFeedForwardPreProcessor,
        Cnn3DToFeedForwardPreProcessor,
        RnnToFeedForwardPreProcessor,
        FeedForwardToRnnPreProcessor,
    ]
}



@dataclasses.dataclass(frozen=True)
class PermuteLayer(LayerConf):
    """Axis permutation of the non-batch dims (Keras Permute parity; the
    reference maps it through KerasPermute -> PermutePreprocessor).
    ``dims`` are 1-indexed non-batch axes, Keras convention."""

    dims: tuple = ()

    def output_type(self, itype):
        if itype.kind == "recurrent" and tuple(self.dims) == (2, 1):
            return InputType.recurrent(itype.timesteps, itype.size)
        if itype.kind == "convolutional" and len(self.dims) == 3:
            hwc = (itype.height, itype.width, itype.channels)
            ph, pw, pc = (hwc[d - 1] for d in self.dims)
            return InputType.convolutional(ph, pw, pc)
        if itype.kind == "feedforward":
            return itype
        raise ValueError(
            f"PermuteLayer: cannot infer the permuted shape for dims "
            f"{self.dims} on a {itype.kind} input")


@dataclasses.dataclass(frozen=True)
class ReshapeLayer(LayerConf):
    """Batch-preserving reshape (KerasReshape -> ReshapePreprocessor
    parity). ``target_shape`` excludes the batch dim; -1 infers."""

    target_shape: tuple = ()

    def output_type(self, itype):
        flat = itype.flat_size()
        shape = list(self.target_shape)
        if -1 in shape:
            known = 1
            for s in shape:
                if s != -1:
                    known *= int(s)
            shape[shape.index(-1)] = flat // max(known, 1)
        if len(shape) == 1:
            return InputType.feed_forward(shape[0])
        if len(shape) == 2:
            return InputType.recurrent(shape[1], shape[0])
        if len(shape) == 3:
            return InputType.convolutional(shape[0], shape[1], shape[2])
        return InputType.feed_forward(flat)


@dataclasses.dataclass(frozen=True)
class LayerNormalization(LayerConf):
    """Trailing-axis layer norm with learned gain/bias — the Keras
    LayerNormalization surface (the reference's samediff layer_norm op,
    libnd4j ops/declarable/generic/nn/layer_norm.cpp, as a layer)."""

    n_out: int = 0
    eps: float = 1e-3

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class GroupNormalization(LayerConf):
    """Group norm over the channel axis (Keras GroupNormalization parity);
    groups=-1 degenerates to instance norm, groups=1 to layer norm over
    spatial+channel."""

    n_out: int = 0
    groups: int = 32
    eps: float = 1e-3

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class RescaleLayer(LayerConf):
    """out = x * scale + offset with per-feature broadcast — the Keras
    Rescaling / adapted-Normalization preprocessing surface."""

    scale: Any = 1.0
    offset: Any = 0.0


@dataclasses.dataclass(frozen=True)
class DiscretizationLayer(LayerConf):
    """Keras Discretization surface: values → bin indices (int32) by the
    given boundaries; pairs with CategoryEncodingLayer for tabular nets."""

    bin_boundaries: Tuple[float, ...] = ()

    def output_type(self, itype):
        return itype


@dataclasses.dataclass(frozen=True)
class CategoryEncodingLayer(LayerConf):
    """Keras CategoryEncoding surface: int ids → one_hot / multi_hot /
    count vectors of width num_tokens."""

    num_tokens: int = 0
    output_mode: str = "multi_hot"

    def output_type(self, itype):
        return InputType.feed_forward(self.num_tokens)


@dataclasses.dataclass(frozen=True)
class EinsumDenseLayer(LayerConf):
    """Keras EinsumDense surface: out = einsum(equation, x, W) (+ bias on
    ``bias_axes``). The workhorse projection of keras-nlp transformer
    blocks; equation uses '...' for batch dims (e.g. '...d,de->...e')."""

    equation: str = ""
    out_shape: Tuple[int, ...] = ()      # W/output dims (no batch dims)
    bias_shape: Tuple[int, ...] = ()     # () = no bias

    def output_type(self, itype):
        import math

        eq = self.equation.replace(" ", "")
        out_spec = eq.split("->")[1]
        if itype.kind == "recurrent":
            # '...' preserves the (batch, time) prefix; explicit specs keep
            # recurrent shape only when the output is still rank-3
            if "..." in out_spec or len(out_spec) >= 3:
                return InputType.recurrent(int(self.out_shape[-1]),
                                           itype.timesteps)
            return InputType.feed_forward(int(self.out_shape[-1]))
        return InputType.feed_forward(int(math.prod(self.out_shape))
                                      if self.out_shape else itype.flat_size())

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class UnitNormLayer(LayerConf):
    """L2-normalize along the trailing axis (Keras UnitNormalization)."""

    eps: float = 1e-12


@dataclasses.dataclass(frozen=True)
class ConvLSTM2D(LayerConf):
    """Convolutional LSTM over (N, T, H, W, C) — KerasConvLSTM2D parity
    (the reference maps it onto its ConvLSTM; here gates are conv2d ops
    inside one lax.scan, so the MXU sees batched convs per step).

    Keras gate order i, f, c, o re-packs to our i, f, o, g at import."""

    n_in: int = 0
    filters: int = 0
    kernel: tuple = (3, 3)
    padding: str = "same"
    return_sequences: bool = False
    gate_activation: str = "sigmoid"

    def has_params(self):
        return True

    def output_type(self, itype):
        if self.padding not in ("same", "truncate", "valid"):
            raise ValueError(f"ConvLSTM2D padding {self.padding!r}")
        h, w = itype.height, itype.width
        if self.padding in ("truncate", "valid"):
            h = h - self.kernel[0] + 1
            w = w - self.kernel[1] + 1
        if self.return_sequences:
            return InputType("convolutional3d", depth=itype.depth or -1,
                             height=h, width=w, channels=self.filters)
        return InputType.convolutional(h, w, self.filters)



@dataclasses.dataclass(frozen=True)
class DotAttentionLayer(LayerConf):
    """Param-free Keras Attention / AdditiveAttention surface: multi-input
    (query, value[, key]) in KERAS order. ``additive`` picks Bahdanau
    scoring (tanh(q+k) reduced by ``scale`` when use_scale)."""

    use_scale: bool = False
    additive: bool = False
    scale: Any = None  # adapted scale vector (AdditiveAttention weights)

    def output_type(self, itype):
        return itype


@dataclasses.dataclass(frozen=True)
class SeparableConvolution1D(LayerConf):
    """Depthwise + pointwise temporal conv over (N, T, C) — the Keras
    SeparableConv1D surface (reference SeparableConvolution2D.java family,
    one dim down)."""

    n_in: int = 0
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    convolution_mode: str = "truncate"
    depth_multiplier: int = 1
    has_bias: bool = True

    def output_type(self, itype):
        t = itype.timesteps
        if t and t > 0:
            if self.convolution_mode == "same":
                t = -(-t // self.stride)
            else:
                t = (t - self.kernel) // self.stride + 1
        return InputType.recurrent(self.n_out, t)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class Deconvolution1D(LayerConf):
    """Transposed temporal conv over (N, T, C) — Keras Conv1DTranspose
    surface (Deconvolution2D.java family, one dim down)."""

    n_in: int = 0
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def output_type(self, itype):
        t = itype.timesteps
        if t and t > 0:
            if self.convolution_mode == "same":
                t = t * self.stride
            else:
                t = (t - 1) * self.stride + self.kernel
        return InputType.recurrent(self.n_out, t)

    def has_params(self):
        return True


@dataclasses.dataclass(frozen=True)
class SpaceToDepthLayer(LayerConf):
    """conf/layers/SpaceToDepthLayer.java: (N,H,W,C) -> (N,H/b,W/b,C*b*b)
    — the YOLOv2 passthrough/reorg block."""

    block_size: int = 2

    def output_type(self, itype):
        b = self.block_size
        return InputType.convolutional(itype.height // b, itype.width // b,
                                       itype.channels * b * b)


@dataclasses.dataclass(frozen=True)
class SameDiffLayer(LayerConf):
    """conf/layers/samediff/SameDiffLayer.java: a user-defined SameDiff
    block inside a MultiLayerNetwork/ComputationGraph stack.

    ``define(sd, x, params) -> SDVariable`` builds the block's op graph
    from an input SDVariable and a dict of parameter SDVariables (declared
    via ``param_shapes``); the outer network differentiates through it like
    any native layer. NOTE: holds a callable — JSON round-trip is not
    supported for this layer (the reference serializes the subclass by
    classname, which has no analog for ad-hoc Python callables)."""

    define: Any = None
    param_shapes: Any = None  # dict name -> shape tuple
    n_out: int = 0

    def output_type(self, itype):
        if self.n_out:
            if itype.kind == "recurrent":
                return InputType.recurrent(self.n_out, itype.timesteps)
            return InputType.feed_forward(self.n_out)
        return itype

    def has_params(self):
        return bool(self.param_shapes)


@dataclasses.dataclass(frozen=True)
class ResizeLayer(LayerConf):
    """Spatial resize to a fixed (height, width) — the Keras Resizing
    preprocessing surface over the registry resize ops."""

    height: int = 0
    width: int = 0
    method: str = "bilinear"  # bilinear | nearest | bicubic

    def output_type(self, itype):
        return InputType.convolutional(self.height, self.width,
                                       itype.channels)


@dataclasses.dataclass(frozen=True)
class CenterCropLayer(LayerConf):
    """Center crop to (height, width) — Keras CenterCrop parity."""

    height: int = 0
    width: int = 0

    def output_type(self, itype):
        return InputType.convolutional(self.height, self.width,
                                       itype.channels)

LAYER_TYPES = {
    c.__name__: c
    for c in [
        CategoryEncodingLayer,
        DiscretizationLayer,
        EinsumDenseLayer,
        DuelingQLayer,
        MoELayer,
        ResizeLayer,
        CenterCropLayer,
        SameDiffLayer,
        SpaceToDepthLayer,
        Deconvolution1D,
        SeparableConvolution1D,
        DotAttentionLayer,
        PermuteLayer,
        ReshapeLayer,
        LayerNormalization,
        GroupNormalization,
        RescaleLayer,
        UnitNormLayer,
        ConvLSTM2D,
        DenseLayer,
        OutputLayer,
        LossLayer,
        EmbeddingLayer,
        EmbeddingSequenceLayer,
        ConvolutionLayer,
        Deconvolution2D,
        DepthwiseConvolution2D,
        SeparableConvolution2D,
        SubsamplingLayer,
        Upsampling2D,
        GlobalPoolingLayer,
        BatchNormalization,
        LocalResponseNormalization,
        ActivationLayer,
        DropoutLayer,
        LSTM,
        GravesLSTM,
        GRU,
        SimpleRnn,
        Bidirectional,
        RnnOutputLayer,
        LastTimeStep,
        SelfAttentionLayer,
        AttentionVertex,
        LearnedSelfAttentionLayer,
        RecurrentAttentionLayer,
        Convolution1D,
        Convolution3D,
        Subsampling3DLayer,
        LocallyConnected2D,
        LocallyConnected1D,
        PReLULayer,
        VariationalAutoencoder,
        ZeroPadding1DLayer,
        ZeroPaddingLayer,
        ZeroPadding3DLayer,
        Cropping1D,
        Cropping2D,
        Cropping3D,
        Upsampling1D,
        Upsampling3D,
        Subsampling1DLayer,
        Deconvolution3D,
        CnnLossLayer,
        RnnLossLayer,
        MaskLayer,
        MaskZeroLayer,
        RepeatVector,
        ElementWiseMultiplicationLayer,
        FrozenLayerWithBackprop,
        CenterLossOutputLayer,
        Yolo2OutputLayer,
        PrimaryCapsules,
        CapsuleLayer,
        CapsuleStrengthLayer,
    ]
}


# ---------------------------------------------------------------------------
# Network-level configuration + builder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MultiLayerConfiguration:
    """MultiLayerConfiguration.java analog: ordered layers + global defaults.

    ``input_type`` drives build-time shape inference (setInputType analog):
    n_in fields left at 0 are filled in, and preprocessors are auto-inserted
    exactly where the reference's InputType logic would put them.
    """

    layers: List[LayerConf] = dataclasses.field(default_factory=list)
    preprocessors: Dict[int, InputPreProcessor] = dataclasses.field(default_factory=dict)
    input_type: Optional[InputType] = None
    seed: int = 0
    updater: Any = dataclasses.field(default_factory=Adam)
    activation: str = "identity"
    weight_init: str = "xavier"
    l1: float = 0.0
    l2: float = 0.0
    weight_decay: float = 0.0
    dtype: str = "float32"
    gradient_normalization: Optional[str] = None  # None|clip_l2_per_layer|clip_value|clip_l2_global
    gradient_normalization_threshold: float = 1.0
    tbptt_fwd_length: int = -1
    tbptt_back_length: int = -1
    backprop_type: str = "standard"  # standard | tbptt

    # ---- JSON round trip --------------------------------------------------
    def to_json(self) -> str:
        return json.dumps(
            {
                "layers": [l.to_dict() for l in self.layers],
                "preprocessors": {str(k): v.to_dict() for k, v in self.preprocessors.items()},
                "input_type": self.input_type.to_dict() if self.input_type else None,
                "seed": self.seed,
                "updater": {"__updater__": get_updater(self.updater).to_dict()},
                "activation": self.activation,
                "weight_init": self.weight_init,
                "l1": self.l1,
                "l2": self.l2,
                "weight_decay": self.weight_decay,
                "dtype": self.dtype,
                "gradient_normalization": self.gradient_normalization,
                "gradient_normalization_threshold": self.gradient_normalization_threshold,
                "tbptt_fwd_length": self.tbptt_fwd_length,
                "tbptt_back_length": self.tbptt_back_length,
                "backprop_type": self.backprop_type,
            },
            indent=2,
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        conf = MultiLayerConfiguration(
            layers=[LayerConf.from_dict(l) for l in d["layers"]],
            preprocessors={
                int(k): InputPreProcessor.from_dict(v)
                for k, v in d.get("preprocessors", {}).items()
            },
            input_type=InputType.from_dict(d["input_type"]) if d.get("input_type") else None,
            seed=d.get("seed", 0),
            updater=Updater.from_dict(d["updater"]["__updater__"]),
            activation=d.get("activation", "identity"),
            weight_init=d.get("weight_init", "xavier"),
            l1=d.get("l1", 0.0),
            l2=d.get("l2", 0.0),
            weight_decay=d.get("weight_decay", 0.0),
            dtype=d.get("dtype", "float32"),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            tbptt_fwd_length=d.get("tbptt_fwd_length", -1),
            tbptt_back_length=d.get("tbptt_back_length", -1),
            backprop_type=d.get("backprop_type", "standard"),
        )
        return conf

    # ---- defaults resolution ---------------------------------------------
    def layer_activation(self, lc: LayerConf) -> str:
        return lc.activation if lc.activation is not None else self.activation

    def layer_weight_init(self, lc: LayerConf) -> str:
        return lc.weight_init if lc.weight_init is not None else self.weight_init

    def layer_updater(self, lc: LayerConf) -> Updater:
        return get_updater(lc.updater) if lc.updater is not None else get_updater(self.updater)

    def layer_l1(self, lc: LayerConf) -> float:
        return lc.l1 if lc.l1 is not None else self.l1

    def layer_l2(self, lc: LayerConf) -> float:
        return lc.l2 if lc.l2 is not None else self.l2

    def layer_weight_decay(self, lc: LayerConf) -> float:
        return lc.weight_decay if lc.weight_decay is not None else self.weight_decay


class NeuralNetConfigurationBuilder:
    """NeuralNetConfiguration.Builder + ListBuilder in one fluent object.

    Mirrors the reference usage:
        conf = (NeuralNetConfiguration.builder().seed(42).updater(Adam(1e-3))
                .list()
                .layer(ConvolutionLayer(...)).layer(...)
                .set_input_type(InputType.convolutional_flat(28, 28, 1))
                .build())
    """

    def __init__(self) -> None:
        self._conf = MultiLayerConfiguration()

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

    def tbptt(self, fwd_length: int, back_length: Optional[int] = None):
        self._conf.backprop_type = "tbptt"
        self._conf.tbptt_fwd_length = fwd_length
        self._conf.tbptt_back_length = back_length or fwd_length
        return self

    def list(self):
        return self

    def layer(self, lc: LayerConf):
        self._conf.layers.append(lc)
        return self

    def input_pre_processor(self, idx: int, p: InputPreProcessor):
        self._conf.preprocessors[idx] = p
        return self

    def set_input_type(self, itype: InputType):
        self._conf.input_type = itype
        return self

    def build(self) -> MultiLayerConfiguration:
        conf = self._conf
        if conf.input_type is not None:
            _infer_shapes(conf)
        return conf


def builder() -> NeuralNetConfigurationBuilder:
    return NeuralNetConfigurationBuilder()


def _infer_shapes(conf: MultiLayerConfiguration) -> None:
    """setInputType analog: fill n_in=0 fields, auto-insert preprocessors."""
    itype = conf.input_type
    new_layers: List[LayerConf] = []
    for i, lc in enumerate(conf.layers):
        itype, lc = _adapt(conf, i, itype, lc)
        new_layers.append(lc)
        itype = lc.output_type(itype)
    conf.layers = new_layers


def _adapt(conf, i, itype, lc) -> Tuple[InputType, LayerConf]:
    """Insert preprocessors & fill n_in for one layer (InputType.getPreProcessorForInputType)."""
    needs_ff = isinstance(lc, (DenseLayer, OutputLayer, EmbeddingLayer))
    is_conv = isinstance(lc, (ConvolutionLayer, SubsamplingLayer, Upsampling2D, LocalResponseNormalization))
    if i not in conf.preprocessors:
        if itype.kind == "convolutionalflat" and is_conv:
            conf.preprocessors[i] = FeedForwardToCnnPreProcessor(
                itype.height, itype.width, itype.channels
            )
            itype = InputType.convolutional(itype.height, itype.width, itype.channels)
        elif itype.kind == "convolutional" and needs_ff:
            conf.preprocessors[i] = CnnToFeedForwardPreProcessor(
                itype.height, itype.width, itype.channels
            )
            itype = InputType.feed_forward(itype.flat_size())
        elif itype.kind == "convolutional3d" and needs_ff:
            conf.preprocessors[i] = Cnn3DToFeedForwardPreProcessor(
                itype.depth, itype.height, itype.width, itype.channels
            )
            itype = InputType.feed_forward(itype.flat_size())
        elif itype.kind == "convolutionalflat" and needs_ff:
            itype = InputType.feed_forward(itype.size)
    else:
        p = conf.preprocessors[i]
        if isinstance(p, FeedForwardToCnnPreProcessor):
            itype = InputType.convolutional(p.height, p.width, p.channels)
        elif isinstance(p, CnnToFeedForwardPreProcessor):
            itype = InputType.feed_forward(p.height * p.width * p.channels)

    # wrapper layers: infer the INNER config's n_in, then rebuild the wrapper
    if isinstance(lc, (Bidirectional, LastTimeStep)):
        inner = lc.inner()
        if getattr(inner, "n_in", 1) == 0:
            size = itype.size if itype.kind == "recurrent" else itype.flat_size()
            inner = dataclasses.replace(inner, n_in=size)
            lc = dataclasses.replace(lc, fwd=inner.to_dict())
        return itype, lc

    # fill n_in / n_out where inferable
    updates: Dict[str, Any] = {}
    if hasattr(lc, "n_in") and getattr(lc, "n_in") == 0:
        if itype.kind in ("feedforward", "convolutionalflat"):
            updates["n_in"] = itype.flat_size()
        elif itype.kind == "recurrent":
            updates["n_in"] = itype.size
        elif itype.kind in ("convolutional", "convolutional3d"):
            updates["n_in"] = itype.channels
    if isinstance(lc, (BatchNormalization, LayerNormalization,
                       GroupNormalization)) and lc.n_out == 0:
        # all three normalize the trailing (feature/channel) axis
        updates["n_out"] = itype.channels \
            if itype.kind in ("convolutional", "convolutional3d") \
            else (itype.size if itype.kind == "recurrent"
                  else itype.flat_size())
    if isinstance(lc, LocallyConnected2D) and tuple(lc.input_size) == (0, 0):
        updates["input_size"] = (itype.height, itype.width)
    if isinstance(lc, LocallyConnected1D) and lc.input_size == 0:
        if not itype.timesteps or itype.timesteps < 0:
            raise ValueError(
                "LocallyConnected1D needs a fixed sequence length — set "
                "input_size or use InputType.recurrent(size, timesteps)")
        updates["input_size"] = itype.timesteps
    if updates:
        lc = dataclasses.replace(lc, **updates)
    return itype, lc
