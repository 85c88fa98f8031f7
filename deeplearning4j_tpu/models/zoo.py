"""Model zoo — deeplearning4j-zoo parity.

Reference parity: org/deeplearning4j/zoo/model/* — LeNet, AlexNet, VGG16/19,
ResNet50, SqueezeNet, Darknet19, TinyYOLO, UNet, SimpleCNN,
InceptionResNetV1, TextGenerationLSTM. Each ZooModel builds a
MultiLayerNetwork or ComputationGraph config; pretrained-weight download does
not exist in this offline environment (initPretrained raises, like the
reference does for models without published weights).

All models use the NHWC internal layout; input shapes quoted in NCHW in the
reference docs map to InputType.convolutional(h, w, c) here.
"""

from __future__ import annotations

from typing import Optional, Tuple

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.nn import conf as C
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph, ElementWiseVertex, GraphBuilder, MergeVertex, ScaleVertex, graph_builder,
)


class ZooModel:
    """ZooModel.java analog."""

    def init(self):
        raise NotImplementedError

    def init_pretrained(self):
        raise NotImplementedError(
            "pretrained weights unavailable offline; train from scratch or "
            "load a checkpoint zip")

    @staticmethod
    def _builder(seed, updater):
        b = nn.builder().seed(seed).weight_init("relu")
        if updater is not None:
            b = b.updater(updater)
        return b


class LeNet(ZooModel):
    """zoo/model/LeNet.java: 2×(conv5+maxpool) + dense 500 + softmax."""

    def __init__(self, num_classes: int = 10, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (28, 28, 1)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        conf = (
            self._builder(self.seed, self.updater).list()
            .layer(nn.ConvolutionLayer(n_out=20, kernel=(5, 5), activation="relu"))
            .layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(nn.ConvolutionLayer(n_out=50, kernel=(5, 5), activation="relu"))
            .layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(nn.DenseLayer(n_out=500, activation="relu"))
            .layer(nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.convolutional_flat(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class SimpleCNN(ZooModel):
    """zoo/model/SimpleCNN.java: small conv stack for sanity workloads."""

    def __init__(self, num_classes: int = 10, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (48, 48, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        conf = (
            self._builder(self.seed, self.updater).list()
            .layer(nn.ConvolutionLayer(n_out=16, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.BatchNormalization())
            .layer(nn.ConvolutionLayer(n_out=16, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(nn.ConvolutionLayer(n_out=32, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.BatchNormalization())
            .layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(nn.GlobalPoolingLayer(pooling_type="avg"))
            .layer(nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class AlexNet(ZooModel):
    """zoo/model/AlexNet.java (single-tower variant)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (224, 224, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Nesterovs(learning_rate=1e-2, momentum=0.9)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        conf = (
            self._builder(self.seed, self.updater).list()
            .layer(nn.ConvolutionLayer(n_out=96, kernel=(11, 11), stride=(4, 4),
                                       activation="relu"))
            .layer(nn.LocalResponseNormalization())
            .layer(nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)))
            .layer(nn.ConvolutionLayer(n_out=256, kernel=(5, 5), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.LocalResponseNormalization())
            .layer(nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)))
            .layer(nn.ConvolutionLayer(n_out=384, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.ConvolutionLayer(n_out=384, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.ConvolutionLayer(n_out=256, kernel=(3, 3), convolution_mode="same",
                                       activation="relu"))
            .layer(nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)))
            .layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class VGG16(ZooModel):
    """zoo/model/VGG16.java: 13 conv + 3 dense."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (224, 224, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Nesterovs(learning_rate=1e-2, momentum=0.9)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        b = self._builder(self.seed, self.updater).list()
        for n_out, reps in [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]:
            for _ in range(reps):
                b = b.layer(nn.ConvolutionLayer(n_out=n_out, kernel=(3, 3),
                                                convolution_mode="same",
                                                activation="relu"))
            b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        conf = (
            b.layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class ResNet50(ZooModel):
    """zoo/model/ResNet50.java: bottleneck residual DAG (ComputationGraph).

    conv1 7×7/2 → maxpool 3×3/2 → stages [3, 4, 6, 3] of bottleneck blocks
    (1×1 → 3×3 → 1×1 ×4 channels, identity or projection shortcut) → global
    avg pool → softmax. BatchNorm after every conv, relu after the residual
    add (standard v1 arrangement, as the reference builds it).
    """

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (224, 224, 3),
                 dtype: str = "float32"):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Nesterovs(learning_rate=1e-1, momentum=0.9)
        self.input_shape = input_shape
        self.dtype = dtype

    def _bottleneck(self, b: GraphBuilder, name: str, inp: str, filters: int,
                    stride: int, project: bool) -> str:
        """One bottleneck block; returns output node name."""
        s = (stride, stride)
        b.add_layer(f"{name}_c1", nn.ConvolutionLayer(
            n_out=filters, kernel=(1, 1), stride=s, convolution_mode="same",
            activation="identity", has_bias=False), inp)
        b.add_layer(f"{name}_bn1", nn.BatchNormalization(activation="relu"), f"{name}_c1")
        b.add_layer(f"{name}_c2", nn.ConvolutionLayer(
            n_out=filters, kernel=(3, 3), convolution_mode="same",
            activation="identity", has_bias=False), f"{name}_bn1")
        b.add_layer(f"{name}_bn2", nn.BatchNormalization(activation="relu"), f"{name}_c2")
        b.add_layer(f"{name}_c3", nn.ConvolutionLayer(
            n_out=4 * filters, kernel=(1, 1), convolution_mode="same",
            activation="identity", has_bias=False), f"{name}_bn2")
        b.add_layer(f"{name}_bn3", nn.BatchNormalization(activation="identity"), f"{name}_c3")
        if project:
            b.add_layer(f"{name}_sc", nn.ConvolutionLayer(
                n_out=4 * filters, kernel=(1, 1), stride=s, convolution_mode="same",
                activation="identity", has_bias=False), inp)
            b.add_layer(f"{name}_scbn", nn.BatchNormalization(activation="identity"),
                        f"{name}_sc")
            shortcut = f"{name}_scbn"
        else:
            shortcut = inp
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), f"{name}_bn3", shortcut)
        b.add_layer(f"{name}_out", nn.ActivationLayer(activation="relu"), f"{name}_add")
        return f"{name}_out"

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        b = (graph_builder().seed(self.seed).updater(self.updater)
             .weight_init("relu").dtype(self.dtype)
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))
        b.add_layer("conv1", nn.ConvolutionLayer(
            n_out=64, kernel=(7, 7), stride=(2, 2), convolution_mode="same",
            activation="identity", has_bias=False,
            s2d_stem=(h % 2 == 0 and w % 2 == 0)), "input")
        b.add_layer("bn1", nn.BatchNormalization(activation="relu"), "conv1")
        b.add_layer("pool1", nn.SubsamplingLayer(
            kernel=(3, 3), stride=(2, 2), convolution_mode="same"), "bn1")
        node = "pool1"
        stages = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
        for si, (filters, blocks, stride) in enumerate(stages):
            for bi in range(blocks):
                node = self._bottleneck(
                    b, f"res{si}_{bi}", node, filters,
                    stride if bi == 0 else 1, project=(bi == 0))
        b.add_layer("gap", nn.GlobalPoolingLayer(pooling_type="avg"), node)
        b.add_layer("fc", nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                         loss="mcxent"), "gap")
        b.set_outputs("fc")
        return ComputationGraph(b.build()).init()


class Darknet19(ZooModel):
    """zoo/model/Darknet19.java: 19-conv backbone (YOLO family)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (224, 224, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Nesterovs(learning_rate=1e-3, momentum=0.9)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape

        def conv(b, n, k):
            return b.layer(nn.ConvolutionLayer(
                n_out=n, kernel=(k, k), convolution_mode="same",
                activation="identity", has_bias=False)) \
                .layer(nn.BatchNormalization(activation="leakyrelu"))

        b = self._builder(self.seed, self.updater).list()
        b = conv(b, 32, 3)
        b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        b = conv(b, 64, 3)
        b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        b = conv(conv(conv(b, 128, 3), 64, 1), 128, 3)
        b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        b = conv(conv(conv(b, 256, 3), 128, 1), 256, 3)
        b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        b = conv(conv(conv(conv(conv(b, 512, 3), 256, 1), 512, 3), 256, 1), 512, 3)
        b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        b = conv(conv(conv(conv(conv(b, 1024, 3), 512, 1), 1024, 3), 512, 1), 1024, 3)
        conf = (
            b.layer(nn.ConvolutionLayer(n_out=self.num_classes, kernel=(1, 1),
                                        convolution_mode="same", activation="identity"))
            .layer(nn.GlobalPoolingLayer(pooling_type="avg"))
            .layer(nn.LossLayer(activation="softmax", loss="mcxent"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class UNet(ZooModel):
    """zoo/model/UNet.java: encoder-decoder with skip connections (DAG)."""

    def __init__(self, n_channels_out: int = 1, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (128, 128, 1), base: int = 16):
        self.n_channels_out = n_channels_out
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape
        self.base = base

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        f = self.base
        b = (graph_builder().seed(self.seed).updater(self.updater).weight_init("relu")
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))

        def double_conv(name, inp, n):
            b.add_layer(f"{name}_a", nn.ConvolutionLayer(
                n_out=n, kernel=(3, 3), convolution_mode="same", activation="relu"), inp)
            b.add_layer(f"{name}_b", nn.ConvolutionLayer(
                n_out=n, kernel=(3, 3), convolution_mode="same", activation="relu"),
                f"{name}_a")
            return f"{name}_b"

        e1 = double_conv("enc1", "input", f)
        b.add_layer("pool1", nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)), e1)
        e2 = double_conv("enc2", "pool1", f * 2)
        b.add_layer("pool2", nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)), e2)
        mid = double_conv("mid", "pool2", f * 4)
        b.add_layer("up2", nn.Upsampling2D(size=(2, 2)), mid)
        b.add_vertex("cat2", MergeVertex(), "up2", e2)
        d2 = double_conv("dec2", "cat2", f * 2)
        b.add_layer("up1", nn.Upsampling2D(size=(2, 2)), d2)
        b.add_vertex("cat1", MergeVertex(), "up1", e1)
        d1 = double_conv("dec1", "cat1", f)
        b.add_layer("out", nn.ConvolutionLayer(
            n_out=self.n_channels_out, kernel=(1, 1), convolution_mode="same",
            activation="sigmoid"), d1)
        b.set_outputs("out")
        return ComputationGraph(b.build()).init()


class TextGenerationLSTM(ZooModel):
    """zoo/model/TextGenerationLSTM.java: char-level 2×LSTM."""

    def __init__(self, vocab_size: int, hidden: int = 256, seed: int = 123, updater=None):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.seed = seed
        self.updater = updater or nn.RmsProp(learning_rate=1e-2)

    def init(self) -> nn.MultiLayerNetwork:
        conf = (
            nn.builder().seed(self.seed).updater(self.updater).weight_init("xavier")
            .list()
            .layer(nn.LSTM(n_out=self.hidden, activation="tanh"))
            .layer(nn.LSTM(n_out=self.hidden, activation="tanh"))
            .layer(nn.RnnOutputLayer(n_out=self.vocab_size, activation="softmax",
                                     loss="mcxent"))
            .set_input_type(nn.InputType.recurrent(self.vocab_size))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class GPT(ZooModel):
    """Decoder-only generative transformer (models/gpt.py) — the zoo entry
    for the continuous-batching serving tier (docs/SERVING.md). No reference
    Java analog: the reference zoo stops at TextGenerationLSTM; this is the
    TPU-native step past it. ``init()`` returns a ``GptModel`` (raw-pytree
    model like BERT, not a MultiLayerNetwork); serve it through
    ``serving.GenerativeEngine`` / ``ParallelInference.generative``."""

    def __init__(self, preset: str = "tiny", seed: int = 0, **overrides):
        from deeplearning4j_tpu.models.gpt import GptConfig

        if preset not in ("tiny", "base"):
            raise ValueError(f"unknown GPT preset {preset!r} "
                             "(known: tiny, base)")
        self.cfg = (GptConfig.tiny(**overrides) if preset == "tiny"
                    else GptConfig.base(**overrides))
        self.seed = seed

    def init(self):
        from deeplearning4j_tpu.models.gpt import GptModel

        return GptModel(self.cfg, seed=self.seed)

    def init_draft(self, seed: int = None, **overrides):
        """The paired DRAFT model for speculative decoding against this
        target (docs/SERVING.md § Speculative decoding): GPT-tiny dims
        sharing the target's vocab/eos/max_position —
        ``GenerativeEngine(model, spec_k=K, draft_model=zoo_gpt.
        init_draft())`` is the whole wiring. A production draft loads
        trained weights into the same config via ``restore_gpt``."""
        from deeplearning4j_tpu.models.gpt import GptModel, draft_config_for

        return GptModel(draft_config_for(self.cfg, **overrides),
                        seed=self.seed if seed is None else seed)


class VGG19(ZooModel):
    """zoo/model/VGG19.java: 16 conv + 3 dense (VGG16 with one extra conv
    in each of the last three stages)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (224, 224, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Nesterovs(learning_rate=1e-2, momentum=0.9)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        b = self._builder(self.seed, self.updater).list()
        for n_out, reps in [(64, 2), (128, 2), (256, 4), (512, 4), (512, 4)]:
            for _ in range(reps):
                b = b.layer(nn.ConvolutionLayer(n_out=n_out, kernel=(3, 3),
                                                convolution_mode="same",
                                                activation="relu"))
            b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        conf = (
            b.layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(nn.OutputLayer(n_out=self.num_classes, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()


class SqueezeNet(ZooModel):
    """zoo/model/SqueezeNet.java (v1.1): fire modules — 1×1 squeeze then
    parallel 1×1/3×3 expands concatenated (MergeVertex DAG)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (227, 227, 3)):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape

    def _fire(self, b: GraphBuilder, name: str, inp: str, squeeze: int,
              expand: int) -> str:
        b.add_layer(f"{name}_sq", nn.ConvolutionLayer(
            n_out=squeeze, kernel=(1, 1), activation="relu",
            convolution_mode="same"), inp)
        b.add_layer(f"{name}_e1", nn.ConvolutionLayer(
            n_out=expand, kernel=(1, 1), activation="relu",
            convolution_mode="same"), f"{name}_sq")
        b.add_layer(f"{name}_e3", nn.ConvolutionLayer(
            n_out=expand, kernel=(3, 3), activation="relu",
            convolution_mode="same"), f"{name}_sq")
        b.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_e1", f"{name}_e3")
        return f"{name}_cat"

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        b = (graph_builder().seed(self.seed).updater(self.updater)
             .weight_init("relu")
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))
        b.add_layer("conv1", nn.ConvolutionLayer(
            n_out=64, kernel=(3, 3), stride=(2, 2), activation="relu",
            convolution_mode="valid"), "input")
        b.add_layer("pool1", nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
                    "conv1")
        node = self._fire(b, "fire2", "pool1", 16, 64)
        node = self._fire(b, "fire3", node, 16, 64)
        b.add_layer("pool3", nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
                    node)
        node = self._fire(b, "fire4", "pool3", 32, 128)
        node = self._fire(b, "fire5", node, 32, 128)
        b.add_layer("pool5", nn.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
                    node)
        node = self._fire(b, "fire6", "pool5", 48, 192)
        node = self._fire(b, "fire7", node, 48, 192)
        node = self._fire(b, "fire8", node, 64, 256)
        node = self._fire(b, "fire9", node, 64, 256)
        b.add_layer("drop9", nn.DropoutLayer(rate=0.5), node)
        b.add_layer("conv10", nn.ConvolutionLayer(
            n_out=self.num_classes, kernel=(1, 1), activation="relu",
            convolution_mode="same"), "drop9")
        b.add_layer("gap", nn.GlobalPoolingLayer(pooling_type="avg"), "conv10")
        b.add_layer("out", nn.LossLayer(loss="mcxent", activation="softmax"), "gap")
        b.set_outputs("out")
        return ComputationGraph(b.build()).init()


class Xception(ZooModel):
    """zoo/model/Xception.java: separable-conv stacks with residual
    projection shortcuts (entry/middle/exit flows; middle-flow repeat count
    is configurable so tests stay small)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (299, 299, 3),
                 middle_repeats: int = 8):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape
        self.middle_repeats = middle_repeats

    def _sep_bn(self, b, name, inp, n_out, relu_first=True):
        if relu_first:
            b.add_layer(f"{name}_act", nn.ActivationLayer(activation="relu"), inp)
            inp = f"{name}_act"
        b.add_layer(f"{name}_sep", nn.SeparableConvolution2D(
            n_out=n_out, kernel=(3, 3), convolution_mode="same",
            activation="identity", has_bias=False), inp)
        b.add_layer(f"{name}_bn", nn.BatchNormalization(activation="identity"),
                    f"{name}_sep")
        return f"{name}_bn"

    def _entry_block(self, b, name, inp, n_out, first_relu=True):
        node = self._sep_bn(b, f"{name}_a", inp, n_out, relu_first=first_relu)
        node = self._sep_bn(b, f"{name}_b", node, n_out)
        b.add_layer(f"{name}_pool", nn.SubsamplingLayer(
            kernel=(3, 3), stride=(2, 2), convolution_mode="same"), node)
        b.add_layer(f"{name}_sc", nn.ConvolutionLayer(
            n_out=n_out, kernel=(1, 1), stride=(2, 2), convolution_mode="same",
            activation="identity", has_bias=False), inp)
        b.add_layer(f"{name}_scbn", nn.BatchNormalization(activation="identity"),
                    f"{name}_sc")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"),
                     f"{name}_pool", f"{name}_scbn")
        return f"{name}_add"

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        b = (graph_builder().seed(self.seed).updater(self.updater)
             .weight_init("relu")
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))
        b.add_layer("conv1", nn.ConvolutionLayer(
            n_out=32, kernel=(3, 3), stride=(2, 2), activation="identity",
            convolution_mode="same", has_bias=False), "input")
        b.add_layer("bn1", nn.BatchNormalization(activation="relu"), "conv1")
        b.add_layer("conv2", nn.ConvolutionLayer(
            n_out=64, kernel=(3, 3), activation="identity",
            convolution_mode="same", has_bias=False), "bn1")
        b.add_layer("bn2", nn.BatchNormalization(activation="relu"), "conv2")
        node = self._entry_block(b, "entry1", "bn2", 128, first_relu=False)
        node = self._entry_block(b, "entry2", node, 256)
        node = self._entry_block(b, "entry3", node, 728)
        for i in range(self.middle_repeats):
            inp = node
            m = self._sep_bn(b, f"mid{i}_a", inp, 728)
            m = self._sep_bn(b, f"mid{i}_b", m, 728)
            m = self._sep_bn(b, f"mid{i}_c", m, 728)
            b.add_vertex(f"mid{i}_add", ElementWiseVertex(op="add"), m, inp)
            node = f"mid{i}_add"
        # exit block (Xception.java block13): sepconv 728 then 1024, with a
        # 1024-channel projection shortcut
        inp = node
        node = self._sep_bn(b, "exit1_a", inp, 728)
        node = self._sep_bn(b, "exit1_b", node, 1024)
        b.add_layer("exit1_pool", nn.SubsamplingLayer(
            kernel=(3, 3), stride=(2, 2), convolution_mode="same"), node)
        b.add_layer("exit1_sc", nn.ConvolutionLayer(
            n_out=1024, kernel=(1, 1), stride=(2, 2), convolution_mode="same",
            activation="identity", has_bias=False), inp)
        b.add_layer("exit1_scbn", nn.BatchNormalization(activation="identity"),
                    "exit1_sc")
        b.add_vertex("exit1_add", ElementWiseVertex(op="add"),
                     "exit1_pool", "exit1_scbn")
        node = "exit1_add"
        node = self._sep_bn(b, "exit2", node, 1536)
        b.add_layer("exit2_relu", nn.ActivationLayer(activation="relu"), node)
        node = self._sep_bn(b, "exit3", "exit2_relu", 2048)
        b.add_layer("exit3_relu", nn.ActivationLayer(activation="relu"), node)
        b.add_layer("gap", nn.GlobalPoolingLayer(pooling_type="avg"),
                    "exit3_relu")
        b.add_layer("fc", nn.OutputLayer(n_out=self.num_classes,
                                         activation="softmax", loss="mcxent"),
                    "gap")
        b.set_outputs("fc")
        return ComputationGraph(b.build()).init()


class TinyYOLO(ZooModel):
    """zoo/model/TinyYOLO.java: darknet-tiny backbone → 1×1 detection conv
    emitting B·(5+C) channels per cell.

    The reference appends Yolo2OutputLayer (anchor-box decode + multi-part
    YOLOv2 loss); here the head is the raw detection tensor plus
    ``yolo_loss`` implementing the same sum-squared objective
    (coords/obj/noobj/class) against (N, H, W, B, 5+C) targets — training
    runs through MultiLayerNetwork.fit with this loss via LossLayer("mse")
    replaced by the external objective (see tests)."""

    def __init__(self, num_classes: int = 20, num_boxes: int = 5,
                 seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (416, 416, 3)):
        self.num_classes = num_classes
        self.num_boxes = num_boxes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape

    def init(self) -> nn.MultiLayerNetwork:
        h, w, c = self.input_shape
        b = self._builder(self.seed, self.updater).list()
        filters = [16, 32, 64, 128, 256]
        for f in filters:
            b = b.layer(nn.ConvolutionLayer(
                n_out=f, kernel=(3, 3), convolution_mode="same",
                activation="identity", has_bias=False))
            b = b.layer(nn.BatchNormalization(activation="leakyrelu"))
            b = b.layer(nn.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        for f in (512, 1024):
            b = b.layer(nn.ConvolutionLayer(
                n_out=f, kernel=(3, 3), convolution_mode="same",
                activation="identity", has_bias=False))
            b = b.layer(nn.BatchNormalization(activation="leakyrelu"))
        depth = self.num_boxes * (5 + self.num_classes)
        conf = (
            b.layer(nn.ConvolutionLayer(n_out=depth, kernel=(1, 1),
                                        convolution_mode="same",
                                        activation="identity"))
            .set_input_type(nn.InputType.convolutional(h, w, c))
            .build()
        )
        return nn.MultiLayerNetwork(conf).init()

    def yolo_loss(self, pred, target, *, lambda_coord: float = 5.0,
                  lambda_noobj: float = 0.5):
        """YOLOv2-style sum-squared loss (Yolo2OutputLayer.computeScore
        analog) — delegates to THE shared implementation (ops/losses.yolo2).
        pred: (N, H, W, B*(5+C)) raw head output; target:
        (N, H, W, B, 5+C) with [x, y, w, h, obj, class-onehot...]."""
        from deeplearning4j_tpu.ops.losses import yolo2

        return yolo2(pred, target, None, lambda_coord=lambda_coord,
                     lambda_noobj=lambda_noobj)


class InceptionResNetV1(ZooModel):
    """zoo/model/InceptionResNetV1.java (the FaceNetNN4-family backbone):
    stem → 5× Inception-ResNet-A → Reduction-A → 10× Inception-ResNet-B →
    Reduction-B → 5× Inception-ResNet-C → avgpool → (dropout) → bottleneck
    embedding + classifier. Block repeat counts are constructor-scaled so
    tests run small."""

    def __init__(self, num_classes: int = 128, seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (160, 160, 3),
                 blocks: Tuple[int, int, int] = (5, 10, 5),
                 embedding_size: int = 128):
        self.num_classes = num_classes
        self.seed = seed
        self.updater = updater or nn.RmsProp(learning_rate=0.1)
        self.input_shape = input_shape
        self.blocks = blocks
        self.embedding_size = embedding_size

    def _conv_bn(self, b, name, inp, n_out, kernel, stride=(1, 1),
                 mode="same"):
        b.add_layer(f"{name}_c", nn.ConvolutionLayer(
            n_out=n_out, kernel=kernel, stride=stride, convolution_mode=mode,
            activation="identity", has_bias=False), inp)
        b.add_layer(f"{name}_bn", nn.BatchNormalization(activation="relu"),
                    f"{name}_c")
        return f"{name}_bn"

    def _block_a(self, b, name, inp, channels):
        b1 = self._conv_bn(b, f"{name}_b1", inp, 32, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2a", inp, 32, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2b", b2, 32, (3, 3))
        b3 = self._conv_bn(b, f"{name}_b3a", inp, 32, (1, 1))
        b3 = self._conv_bn(b, f"{name}_b3b", b3, 32, (3, 3))
        b3 = self._conv_bn(b, f"{name}_b3c", b3, 32, (3, 3))
        b.add_vertex(f"{name}_cat", MergeVertex(), b1, b2, b3)
        b.add_layer(f"{name}_up", nn.ConvolutionLayer(
            n_out=channels, kernel=(1, 1), convolution_mode="same",
            activation="identity"), f"{name}_cat")
        b.add_vertex(f"{name}_scale", ScaleVertex(scale=0.17), f"{name}_up")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp,
                     f"{name}_scale")
        b.add_layer(f"{name}_out", nn.ActivationLayer(activation="relu"),
                    f"{name}_add")
        return f"{name}_out"

    def _block_b(self, b, name, inp, channels):
        b1 = self._conv_bn(b, f"{name}_b1", inp, 128, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2a", inp, 128, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2b", b2, 128, (1, 7))
        b2 = self._conv_bn(b, f"{name}_b2c", b2, 128, (7, 1))
        b.add_vertex(f"{name}_cat", MergeVertex(), b1, b2)
        b.add_layer(f"{name}_up", nn.ConvolutionLayer(
            n_out=channels, kernel=(1, 1), convolution_mode="same",
            activation="identity"), f"{name}_cat")
        b.add_vertex(f"{name}_scale", ScaleVertex(scale=0.10), f"{name}_up")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp,
                     f"{name}_scale")
        b.add_layer(f"{name}_out", nn.ActivationLayer(activation="relu"),
                    f"{name}_add")
        return f"{name}_out"

    def _block_c(self, b, name, inp, channels):
        b1 = self._conv_bn(b, f"{name}_b1", inp, 192, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2a", inp, 192, (1, 1))
        b2 = self._conv_bn(b, f"{name}_b2b", b2, 192, (1, 3))
        b2 = self._conv_bn(b, f"{name}_b2c", b2, 192, (3, 1))
        b.add_vertex(f"{name}_cat", MergeVertex(), b1, b2)
        b.add_layer(f"{name}_up", nn.ConvolutionLayer(
            n_out=channels, kernel=(1, 1), convolution_mode="same",
            activation="identity"), f"{name}_cat")
        b.add_vertex(f"{name}_scale", ScaleVertex(scale=0.20), f"{name}_up")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp,
                     f"{name}_scale")
        b.add_layer(f"{name}_out", nn.ActivationLayer(activation="relu"),
                    f"{name}_add")
        return f"{name}_out"

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        na, nb_, nc = self.blocks
        b = (graph_builder().seed(self.seed).updater(self.updater)
             .weight_init("relu")
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))
        node = self._conv_bn(b, "stem1", "input", 32, (3, 3), (2, 2), "valid")
        node = self._conv_bn(b, "stem2", node, 32, (3, 3), mode="valid")
        node = self._conv_bn(b, "stem3", node, 64, (3, 3))
        b.add_layer("stem_pool", nn.SubsamplingLayer(kernel=(3, 3),
                                                     stride=(2, 2)), node)
        node = self._conv_bn(b, "stem4", "stem_pool", 80, (1, 1), mode="valid")
        node = self._conv_bn(b, "stem5", node, 192, (3, 3), mode="valid")
        node = self._conv_bn(b, "stem6", node, 256, (3, 3), (2, 2), "valid")
        for i in range(na):
            node = self._block_a(b, f"a{i}", node, 256)
        # Reduction-A
        r1 = self._conv_bn(b, "redA_b1", node, 384, (3, 3), (2, 2), "valid")
        r2 = self._conv_bn(b, "redA_b2a", node, 192, (1, 1))
        r2 = self._conv_bn(b, "redA_b2b", r2, 192, (3, 3))
        r2 = self._conv_bn(b, "redA_b2c", r2, 256, (3, 3), (2, 2), "valid")
        b.add_layer("redA_pool", nn.SubsamplingLayer(
            kernel=(3, 3), stride=(2, 2)), node)
        b.add_vertex("redA_cat", MergeVertex(), r1, r2, "redA_pool")
        node = "redA_cat"  # 384+256+256 = 896 channels
        for i in range(nb_):
            node = self._block_b(b, f"b{i}", node, 896)
        # Reduction-B
        r1 = self._conv_bn(b, "redB_b1a", node, 256, (1, 1))
        r1 = self._conv_bn(b, "redB_b1b", r1, 384, (3, 3), (2, 2), "valid")
        r2 = self._conv_bn(b, "redB_b2a", node, 256, (1, 1))
        r2 = self._conv_bn(b, "redB_b2b", r2, 256, (3, 3), (2, 2), "valid")
        r3 = self._conv_bn(b, "redB_b3a", node, 256, (1, 1))
        r3 = self._conv_bn(b, "redB_b3b", r3, 256, (3, 3))
        r3 = self._conv_bn(b, "redB_b3c", r3, 256, (3, 3), (2, 2), "valid")
        b.add_layer("redB_pool", nn.SubsamplingLayer(
            kernel=(3, 3), stride=(2, 2)), node)
        b.add_vertex("redB_cat", MergeVertex(), r1, r2, r3, "redB_pool")
        node = "redB_cat"  # 384+256+256+896 = 1792 channels
        for i in range(nc):
            node = self._block_c(b, f"c{i}", node, 1792)
        b.add_layer("gap", nn.GlobalPoolingLayer(pooling_type="avg"), node)
        b.add_layer("bottleneck", nn.DenseLayer(
            n_out=self.embedding_size, activation="identity",
            has_bias=False), "gap")
        b.add_layer("emb_norm", nn.BatchNormalization(activation="identity"),
                    "bottleneck")
        b.add_layer("out", nn.OutputLayer(n_out=self.num_classes,
                                          activation="softmax",
                                          loss="mcxent"), "emb_norm")
        b.set_outputs("out")
        return ComputationGraph(b.build()).init()


class YOLO2(ZooModel):
    """zoo/model/YOLO2.java: Darknet19 backbone + the YOLOv2 passthrough —
    the 26×26×512 mid-level features reorg (SpaceToDepth block 2) and
    concatenate with the 13×13×1024 deep path before the detection conv
    emitting B·(5+C) channels per cell (same raw-head convention as
    TinyYOLO; pair with ops.losses yolo_loss for training)."""

    def __init__(self, num_classes: int = 80, num_boxes: int = 5,
                 seed: int = 123, updater=None,
                 input_shape: Tuple[int, int, int] = (416, 416, 3)):
        self.num_classes = num_classes
        self.num_boxes = num_boxes
        self.seed = seed
        self.updater = updater or nn.Adam(learning_rate=1e-3)
        self.input_shape = input_shape

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        b = (graph_builder().seed(self.seed).updater(self.updater)
             .weight_init("relu")
             .add_inputs("input")
             .set_input_types(input=nn.InputType.convolutional(h, w, c)))
        idx = 0

        def conv(inp, n, k):
            nonlocal idx
            idx += 1
            b.add_layer(f"c{idx}", nn.ConvolutionLayer(
                n_out=n, kernel=(k, k), convolution_mode="same",
                activation="identity", has_bias=False), inp)
            b.add_layer(f"bn{idx}", nn.BatchNormalization(
                activation="leakyrelu"), f"c{idx}")
            return f"bn{idx}"

        def pool(inp):
            nonlocal idx
            idx += 1
            b.add_layer(f"p{idx}", nn.SubsamplingLayer(
                kernel=(2, 2), stride=(2, 2)), inp)
            return f"p{idx}"

        x = conv("input", 32, 3)
        x = pool(x)
        x = conv(x, 64, 3)
        x = pool(x)
        x = conv(conv(conv(x, 128, 3), 64, 1), 128, 3)
        x = pool(x)
        x = conv(conv(conv(x, 256, 3), 128, 1), 256, 3)
        x = pool(x)
        x = conv(conv(conv(conv(conv(x, 512, 3), 256, 1), 512, 3),
                      256, 1), 512, 3)
        route = x  # 26×26×512 passthrough source
        x = pool(x)
        x = conv(conv(conv(conv(conv(x, 1024, 3), 512, 1), 1024, 3),
                      512, 1), 1024, 3)
        x = conv(conv(x, 1024, 3), 1024, 3)
        # passthrough: 1×1 squeeze → reorg to 13×13×256 → concat
        sq = conv(route, 64, 1)
        b.add_layer("reorg", nn.conf.SpaceToDepthLayer(block_size=2), sq)
        b.add_vertex("route_cat", MergeVertex(), x, "reorg")
        x = conv("route_cat", 1024, 3)
        depth = self.num_boxes * (5 + self.num_classes)
        b.add_layer("detect", nn.ConvolutionLayer(
            n_out=depth, kernel=(1, 1), convolution_mode="same",
            activation="identity"), x)
        b.set_outputs("detect")
        return ComputationGraph(b.build()).init()
