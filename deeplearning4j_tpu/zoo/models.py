"""Zoo model definitions (org/deeplearning4j/zoo/model/*.java parity).

Every model is TPU-first: NHWC layout, fused conv+bn+relu left to XLA,
ResNet/SqueezeNet/UNet expressed on ComputationGraph so the whole DAG traces
into one XLA program. ``compute_dtype='bfloat16'`` puts the convs on the MXU
in bf16 with fp32 params (recommended for benchmarks).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from deeplearning4j_tpu.nn import (
    ComputationGraph,
    InputType,
    MultiLayerNetwork,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    ActivationLayer,
    BatchNormalization,
    ConvolutionLayer,
    Deconvolution2D,
    DenseLayer,
    DropoutLayer,
    GlobalPoolingLayer,
    LocalResponseNormalization,
    OutputLayer,
    SeparableConvolution2D,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.updaters import Adam, Nesterovs
from deeplearning4j_tpu.nn.vertices import ElementWiseVertex, MergeVertex, ScaleVertex


@dataclasses.dataclass
class ZooModel:
    """Base (org/deeplearning4j/zoo/ZooModel.java parity)."""

    num_classes: int = 1000
    seed: int = 12345
    input_shape: Tuple[int, int, int] = (224, 224, 3)  # HWC (NHWC batch layout)
    compute_dtype: str = "float32"
    updater: object = None

    def conf(self):
        raise NotImplementedError

    def init(self):
        """Build + initialize the network (ZooModel.init parity)."""
        conf = self.conf()
        if hasattr(conf, "nodes"):
            return ComputationGraph(conf).init()
        return MultiLayerNetwork(conf).init()

    def pretrained(self, *a, **kw):
        raise NotImplementedError(
            "pretrained weights need network egress (reference downloads from "
            "dl4j blob storage); save/restore locally via ModelSerializer"
        )

    def _builder(self):
        return (
            NeuralNetConfiguration.builder()
            .seed(self.seed)
            .updater(self.updater or Adam(1e-3))
            .compute_dtype(self.compute_dtype)
        )


# ---------------------------------------------------------------------------
# Linear stacks (MultiLayerNetwork)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LeNet(ZooModel):
    """zoo/model/LeNet.java — BASELINE config #1."""

    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (28, 28, 1)

    def conf(self):
        h, w, c = self.input_shape
        return (
            self._builder()
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5), padding="VALID", activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5), padding="VALID", activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_in=500, n_out=self.num_classes))
            .set_input_type(InputType.convolutional(h, w, c))
            .build()
        )


@dataclasses.dataclass
class SimpleCNN(ZooModel):
    """zoo/model/SimpleCNN.java."""

    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (48, 48, 3)

    def conf(self):
        h, w, c = self.input_shape
        return (
            self._builder()
            .list()
            .layer(ConvolutionLayer(n_out=16, kernel_size=(3, 3), activation="relu"))
            .layer(BatchNormalization())
            .layer(ConvolutionLayer(n_out=16, kernel_size=(3, 3), activation="relu"))
            .layer(BatchNormalization())
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(ConvolutionLayer(n_out=32, kernel_size=(3, 3), activation="relu"))
            .layer(BatchNormalization())
            .layer(ConvolutionLayer(n_out=32, kernel_size=(3, 3), activation="relu"))
            .layer(BatchNormalization())
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(DropoutLayer(rate=0.5))
            .layer(GlobalPoolingLayer())
            .layer(OutputLayer(n_in=32, n_out=self.num_classes))
            .set_input_type(InputType.convolutional(h, w, c))
            .build()
        )


@dataclasses.dataclass
class AlexNet(ZooModel):
    """zoo/model/AlexNet.java (one-tower variant)."""

    def conf(self):
        h, w, c = self.input_shape
        return (
            self._builder()
            .list()
            .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11), stride=(4, 4), padding="VALID", activation="relu"))
            .layer(LocalResponseNormalization())
            .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5), activation="relu"))
            .layer(LocalResponseNormalization())
            .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), activation="relu"))
            .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), activation="relu"))
            .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
            .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(DenseLayer(n_in=4096, n_out=4096, activation="relu", dropout=0.5))
            .layer(OutputLayer(n_in=4096, n_out=self.num_classes))
            .set_input_type(InputType.convolutional(h, w, c))
            .build()
        )


def _vgg_blocks(lb, spec):
    for n_convs, channels in spec:
        for _ in range(n_convs):
            lb.layer(ConvolutionLayer(n_out=channels, kernel_size=(3, 3), activation="relu"))
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
    return lb


@dataclasses.dataclass
class VGG16(ZooModel):
    """zoo/model/VGG16.java."""

    spec = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))

    def conf(self):
        h, w, c = self.input_shape
        lb = self._builder().list()
        _vgg_blocks(lb, self.spec)
        return (
            lb.layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
            .layer(DenseLayer(n_in=4096, n_out=4096, activation="relu", dropout=0.5))
            .layer(OutputLayer(n_in=4096, n_out=self.num_classes))
            .set_input_type(InputType.convolutional(h, w, c))
            .build()
        )


@dataclasses.dataclass
class VGG19(VGG16):
    """zoo/model/VGG19.java."""

    spec = ((2, 64), (2, 128), (4, 256), (4, 512), (4, 512))


@dataclasses.dataclass
class Darknet19(ZooModel):
    """zoo/model/Darknet19.java."""

    def conf(self):
        h, w, c = self.input_shape

        def conv_bn(lb, n_out, k):
            lb.layer(ConvolutionLayer(n_out=n_out, kernel_size=(k, k), has_bias=False))
            lb.layer(BatchNormalization())
            lb.layer(ActivationLayer(activation="leakyrelu"))

        lb = self._builder().list()
        conv_bn(lb, 32, 3)
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        conv_bn(lb, 64, 3)
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for a, b_, k in ((128, 64, 3), (256, 128, 3)):
            conv_bn(lb, a, k)
            conv_bn(lb, b_, 1)
            conv_bn(lb, a, k)
            lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for a, b_ in ((512, 256), (1024, 512)):
            conv_bn(lb, a, 3)
            conv_bn(lb, b_, 1)
            conv_bn(lb, a, 3)
            conv_bn(lb, b_, 1)
            conv_bn(lb, a, 3)
            if a == 512:
                lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        lb.layer(ConvolutionLayer(n_out=self.num_classes, kernel_size=(1, 1)))
        lb.layer(GlobalPoolingLayer())
        return (
            lb.layer(OutputLayer(n_in=self.num_classes, n_out=self.num_classes))
            .set_input_type(InputType.convolutional(h, w, c))
            .build()
        )


# ---------------------------------------------------------------------------
# DAG models (ComputationGraph)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResNet50(ZooModel):
    """zoo/model/ResNet50.java — BASELINE config #2 and the flagship bench
    model. ResNet-v1 bottleneck layout (stride on the first 1x1, as in the
    reference/Keras); NHWC; every block is conv→bn→relu chains XLA fuses.

    ``remat_policy``/``stage_barriers`` engage the fusion-boundary subsystem
    (util/xla_tuning.py): residual-stage boundaries (stem, res2–res5) are
    always recorded in the config; a named policy selectively rematerializes
    each stage in the backward pass (save conv outputs, recompute the cheap
    BN/elementwise epilogue), barriers fence XLA fusion at the boundaries.
    The default stays ``None``: no candidate has been timed on a chip yet
    (ROADMAP Speed 2; docs/FUSION_TUNING.md)."""

    updater: object = None
    remat_policy: Optional[str] = None
    stage_barriers: bool = False

    def conf(self):
        h, w, c = self.input_shape
        b = self._builder()
        if self.remat_policy is not None:
            b.remat_policy(self.remat_policy)
        if self.stage_barriers:
            b.stage_barriers(True)
        gb = b.graph_builder().add_inputs("input")

        def conv_bn(name, inp, n_out, k, stride=(1, 1), relu=True, pad="SAME"):
            gb.add_layer(
                f"{name}_conv",
                ConvolutionLayer(n_out=n_out, kernel_size=(k, k), stride=stride,
                                 padding=pad, has_bias=False),
                inp,
            )
            gb.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_conv")
            if relu:
                gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_bn")
                return f"{name}_relu"
            return f"{name}_bn"

        def bottleneck(name, inp, filters, stride, project):
            f1, f2, f3 = filters
            x = conv_bn(f"{name}_a", inp, f1, 1, stride=stride)
            x = conv_bn(f"{name}_b", x, f2, 3)
            x = conv_bn(f"{name}_c", x, f3, 1, relu=False)
            if project:
                sc = conv_bn(f"{name}_sc", inp, f3, 1, stride=stride, relu=False)
            else:
                sc = inp
            gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, sc)
            gb.add_layer(f"{name}_out", ActivationLayer(activation="relu"), f"{name}_add")
            return f"{name}_out"

        x = conv_bn("stem", "input", 64, 7, stride=(2, 2))
        gb.add_layer("stem_pool", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2), padding="SAME"), x)
        x = "stem_pool"
        gb.stage_boundary("stem_pool")
        stages = [
            ("res2", 3, (64, 64, 256), (1, 1)),
            ("res3", 4, (128, 128, 512), (2, 2)),
            ("res4", 6, (256, 256, 1024), (2, 2)),
            ("res5", 3, (512, 512, 2048), (2, 2)),
        ]
        for sname, blocks, filters, stride in stages:
            x = bottleneck(f"{sname}a", x, filters, stride, project=True)
            for i in range(1, blocks):
                x = bottleneck(f"{sname}{chr(ord('a') + i)}", x, filters, (1, 1), project=False)
            gb.stage_boundary(x)  # stage end (res2c_out … res5c_out)
        gb.add_layer("avgpool", GlobalPoolingLayer(), x)
        gb.add_layer("output", OutputLayer(n_in=2048, n_out=self.num_classes), "avgpool")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()


@dataclasses.dataclass
class SqueezeNet(ZooModel):
    """zoo/model/SqueezeNet.java — fire modules on ComputationGraph."""

    def conf(self):
        h, w, c = self.input_shape
        gb = self._builder().graph_builder().add_inputs("input")

        def fire(name, inp, squeeze, expand):
            gb.add_layer(f"{name}_sq", ConvolutionLayer(n_out=squeeze, kernel_size=(1, 1), activation="relu"), inp)
            gb.add_layer(f"{name}_e1", ConvolutionLayer(n_out=expand, kernel_size=(1, 1), activation="relu"), f"{name}_sq")
            gb.add_layer(f"{name}_e3", ConvolutionLayer(n_out=expand, kernel_size=(3, 3), activation="relu"), f"{name}_sq")
            gb.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_e1", f"{name}_e3")
            return f"{name}_cat"

        gb.add_layer("conv1", ConvolutionLayer(n_out=64, kernel_size=(3, 3), stride=(2, 2), padding="VALID", activation="relu"), "input")
        gb.add_layer("pool1", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), "conv1")
        x = fire("fire2", "pool1", 16, 64)
        x = fire("fire3", x, 16, 64)
        gb.add_layer("pool3", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), x)
        x = fire("fire4", "pool3", 32, 128)
        x = fire("fire5", x, 32, 128)
        gb.add_layer("pool5", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), x)
        x = fire("fire6", "pool5", 48, 192)
        x = fire("fire7", x, 48, 192)
        x = fire("fire8", x, 64, 256)
        x = fire("fire9", x, 64, 256)
        gb.add_layer("drop9", DropoutLayer(rate=0.5), x)
        gb.add_layer("conv10", ConvolutionLayer(n_out=self.num_classes, kernel_size=(1, 1), activation="relu"), "drop9")
        gb.add_layer("gap", GlobalPoolingLayer(), "conv10")
        gb.add_layer("output", OutputLayer(n_in=self.num_classes, n_out=self.num_classes), "gap")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()


@dataclasses.dataclass
class UNet(ZooModel):
    """zoo/model/UNet.java — encoder/decoder with skip merges. Output is a
    per-pixel sigmoid map on CnnLossLayer with XENT, as in the reference."""

    num_classes: int = 1
    input_shape: Tuple[int, int, int] = (128, 128, 3)
    base_filters: int = 16  # reference uses 64; configurable for memory

    def conf(self):
        h, w, c = self.input_shape
        f = self.base_filters
        gb = self._builder().graph_builder().add_inputs("input")

        def double_conv(name, inp, n_out):
            gb.add_layer(f"{name}_c1", ConvolutionLayer(n_out=n_out, kernel_size=(3, 3), activation="relu"), inp)
            gb.add_layer(f"{name}_c2", ConvolutionLayer(n_out=n_out, kernel_size=(3, 3), activation="relu"), f"{name}_c1")
            return f"{name}_c2"

        # encoder
        skips = []
        x = "input"
        for i, mult in enumerate((1, 2, 4, 8)):
            x = double_conv(f"enc{i}", x, f * mult)
            skips.append(x)
            gb.add_layer(f"down{i}", SubsamplingLayer(kernel_size=(2, 2)), x)
            x = f"down{i}"
        x = double_conv("mid", x, f * 16)
        # decoder
        for i, mult in zip(range(3, -1, -1), (8, 4, 2, 1)):
            gb.add_layer(f"up{i}", Deconvolution2D(n_out=f * mult, kernel_size=(2, 2), stride=(2, 2), activation="relu"), x)
            gb.add_vertex(f"skip{i}", MergeVertex(), f"up{i}", skips[i])
            x = double_conv(f"dec{i}", f"skip{i}", f * mult)
        from deeplearning4j_tpu.nn.layers_special import CnnLossLayer

        gb.add_layer("logits", ConvolutionLayer(n_out=self.num_classes, kernel_size=(1, 1)), x)
        gb.add_layer("output", CnnLossLayer(loss="xent", activation="sigmoid"), "logits")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()


@dataclasses.dataclass
class Xception(ZooModel):
    """zoo/model/Xception.java — separable convs with residual connections
    (entry/middle/exit flow; middle-flow repeats configurable)."""

    middle_repeats: int = 8

    def conf(self):
        h, w, c = self.input_shape
        gb = self._builder().graph_builder().add_inputs("input")

        def conv_bn(name, inp, n_out, k, stride=(1, 1), relu=True):
            gb.add_layer(f"{name}_conv", ConvolutionLayer(n_out=n_out, kernel_size=(k, k), stride=stride, has_bias=False), inp)
            gb.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_conv")
            if relu:
                gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_bn")
                return f"{name}_relu"
            return f"{name}_bn"

        def sep_bn(name, inp, n_out, relu_before=True):
            src = inp
            if relu_before:
                gb.add_layer(f"{name}_prerelu", ActivationLayer(activation="relu"), inp)
                src = f"{name}_prerelu"
            gb.add_layer(f"{name}_sep", SeparableConvolution2D(n_out=n_out, kernel_size=(3, 3), has_bias=False), src)
            gb.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_sep")
            return f"{name}_bn"

        x = conv_bn("stem1", "input", 32, 3, stride=(2, 2))
        x = conv_bn("stem2", x, 64, 3)
        # entry-flow residual blocks
        for i, n_out in enumerate((128, 256, 728)):
            sc = conv_bn(f"entry{i}_sc", x, n_out, 1, stride=(2, 2), relu=False)
            b = sep_bn(f"entry{i}_s1", x, n_out, relu_before=i > 0)
            b = sep_bn(f"entry{i}_s2", b, n_out)
            gb.add_layer(f"entry{i}_pool", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2), padding="SAME"), b)
            gb.add_vertex(f"entry{i}_add", ElementWiseVertex(op="add"), f"entry{i}_pool", sc)
            x = f"entry{i}_add"
        # middle flow
        for r in range(self.middle_repeats):
            b = x
            for j in range(3):
                b = sep_bn(f"mid{r}_s{j}", b, 728)
            gb.add_vertex(f"mid{r}_add", ElementWiseVertex(op="add"), b, x)
            x = f"mid{r}_add"
        # exit flow
        sc = conv_bn("exit_sc", x, 1024, 1, stride=(2, 2), relu=False)
        b = sep_bn("exit_s1", x, 728)
        b = sep_bn("exit_s2", b, 1024)
        gb.add_layer("exit_pool", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2), padding="SAME"), b)
        gb.add_vertex("exit_add", ElementWiseVertex(op="add"), "exit_pool", sc)
        b = sep_bn("exit_s3", "exit_add", 1536, relu_before=False)
        gb.add_layer("exit_relu3", ActivationLayer(activation="relu"), b)
        b = sep_bn("exit_s4", "exit_relu3", 2048, relu_before=False)
        gb.add_layer("exit_relu4", ActivationLayer(activation="relu"), b)
        gb.add_layer("gap", GlobalPoolingLayer(), "exit_relu4")
        gb.add_layer("output", OutputLayer(n_in=2048, n_out=self.num_classes), "gap")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()


@dataclasses.dataclass
class TextGenerationLSTM(ZooModel):
    """zoo/model/TextGenerationLSTM.java — char-level generation: stacked
    LSTMs + per-timestep softmax (the GravesLSTM char-RNN, BASELINE #3's
    model family). Input (B,T,vocab) one-hot; output per-step distribution."""

    total_unique_characters: int = 47
    units: int = 256
    dropout: float = 0.2
    max_length: int = 40

    def conf(self):
        from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer

        v = self.total_unique_characters
        lb = self._builder().list()
        lb.layer(LSTM(n_in=v, n_out=self.units))
        lb.layer(LSTM(n_in=self.units, n_out=self.units, dropout=self.dropout))
        lb.layer(RnnOutputLayer(n_in=self.units, n_out=v, loss="mcxent",
                                activation="softmax", dropout=self.dropout))
        lb.set_input_type(InputType.recurrent(v, self.max_length))
        return lb.build()


@dataclasses.dataclass
class TinyYOLO(ZooModel):
    """zoo/model/TinyYOLO.java — Darknet-tiny backbone + YOLOv2 head.
    Input HxW divisible by 32; output grid (H/32, W/32)."""

    input_shape: Tuple[int, int, int] = (416, 416, 3)
    num_classes: int = 20
    anchors: tuple = ((1.08, 1.19), (3.42, 4.41), (6.63, 11.38),
                      (9.42, 5.11), (16.62, 10.52))

    def conf(self):
        from deeplearning4j_tpu.nn.objdetect import Yolo2OutputLayer

        h, w, c = self.input_shape
        a = len(self.anchors)
        lb = self._builder().list()

        def conv_bn(n_out, k=3):
            lb.layer(ConvolutionLayer(n_out=n_out, kernel_size=(k, k), has_bias=False))
            lb.layer(BatchNormalization())
            lb.layer(ActivationLayer(activation="leakyrelu"))

        for i, n in enumerate((16, 32, 64, 128, 256)):
            conv_bn(n)
            lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        conv_bn(512)
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(1, 1), padding="SAME"))
        conv_bn(1024)
        conv_bn(1024)
        lb.layer(ConvolutionLayer(n_out=a * (5 + self.num_classes),
                                  kernel_size=(1, 1)))
        lb.layer(Yolo2OutputLayer(anchors=self.anchors))
        lb.set_input_type(InputType.convolutional(h, w, c))
        return lb.build()


@dataclasses.dataclass
class YOLO2(TinyYOLO):
    """zoo/model/YOLO2.java — Darknet-19 backbone + YOLOv2 detection head
    (without the passthrough/reorg skip of the full paper model, like the
    reference's simplified zoo config)."""

    anchors: tuple = ((0.57273, 0.677385), (1.87446, 2.06253),
                      (3.33843, 5.47434), (7.88282, 3.52778),
                      (9.77052, 9.16828))

    def conf(self):
        from deeplearning4j_tpu.nn.objdetect import Yolo2OutputLayer

        h, w, c = self.input_shape
        a = len(self.anchors)
        lb = self._builder().list()

        def conv_bn(n_out, k):
            lb.layer(ConvolutionLayer(n_out=n_out, kernel_size=(k, k), has_bias=False))
            lb.layer(BatchNormalization())
            lb.layer(ActivationLayer(activation="leakyrelu"))

        conv_bn(32, 3)
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        conv_bn(64, 3)
        lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for big, small in ((128, 64), (256, 128)):
            conv_bn(big, 3)
            conv_bn(small, 1)
            conv_bn(big, 3)
            lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for big, small in ((512, 256), (1024, 512)):
            conv_bn(big, 3)
            conv_bn(small, 1)
            conv_bn(big, 3)
            conv_bn(small, 1)
            conv_bn(big, 3)
            if big == 512:
                lb.layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        conv_bn(1024, 3)
        conv_bn(1024, 3)
        lb.layer(ConvolutionLayer(n_out=a * (5 + self.num_classes),
                                  kernel_size=(1, 1)))
        lb.layer(Yolo2OutputLayer(anchors=self.anchors))
        lb.set_input_type(InputType.convolutional(h, w, c))
        return lb.build()


@dataclasses.dataclass
class InceptionResNetV1(ZooModel):
    """zoo/model/InceptionResNetV1.java — the FaceNet embedding network:
    stem + 5x block35 + reduction-A + 10x block17 + reduction-B + 5x block8,
    global pool, 128-d L2-normalized embedding + softmax head."""

    input_shape: Tuple[int, int, int] = (160, 160, 3)
    embedding_size: int = 128

    def conf(self):
        from deeplearning4j_tpu.nn.vertices import L2NormalizeVertex

        h, w, c = self.input_shape
        gb = self._builder().graph_builder().add_inputs("input")
        uid = [0]

        def conv_bn(inp, n_out, k, stride=(1, 1), pad="SAME", relu=True):
            uid[0] += 1
            name = f"cb{uid[0]}"
            gb.add_layer(f"{name}_c", ConvolutionLayer(
                n_out=n_out, kernel_size=(k, k) if isinstance(k, int) else k,
                stride=stride, padding=pad, has_bias=False), inp)
            gb.add_layer(f"{name}_b", BatchNormalization(), f"{name}_c")
            if not relu:
                return f"{name}_b"
            gb.add_layer(f"{name}_r", ActivationLayer(activation="relu"), f"{name}_b")
            return f"{name}_r"

        def block35(inp, scale=0.17):  # Inception-ResNet-A
            uid[0] += 1
            name = f"a{uid[0]}"
            b0 = conv_bn(inp, 32, 1)
            b1 = conv_bn(conv_bn(inp, 32, 1), 32, 3)
            b2 = conv_bn(conv_bn(conv_bn(inp, 32, 1), 32, 3), 32, 3)
            gb.add_vertex(f"{name}_cat", MergeVertex(), b0, b1, b2)
            up = conv_bn(f"{name}_cat", 256, 1, relu=False)
            gb.add_vertex(f"{name}_scale", ScaleVertex(scale=scale), up)
            gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp, f"{name}_scale")
            gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_add")
            return f"{name}_relu"

        def block17(inp, scale=0.10):  # Inception-ResNet-B
            uid[0] += 1
            name = f"b{uid[0]}"
            b0 = conv_bn(inp, 128, 1)
            b1 = conv_bn(conv_bn(conv_bn(inp, 128, 1), 128, (1, 7)), 128, (7, 1))
            gb.add_vertex(f"{name}_cat", MergeVertex(), b0, b1)
            up = conv_bn(f"{name}_cat", 896, 1, relu=False)
            gb.add_vertex(f"{name}_scale", ScaleVertex(scale=scale), up)
            gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp, f"{name}_scale")
            gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_add")
            return f"{name}_relu"

        def block8(inp, scale=0.20):  # Inception-ResNet-C
            uid[0] += 1
            name = f"c{uid[0]}"
            b0 = conv_bn(inp, 192, 1)
            b1 = conv_bn(conv_bn(conv_bn(inp, 192, 1), 192, (1, 3)), 192, (3, 1))
            gb.add_vertex(f"{name}_cat", MergeVertex(), b0, b1)
            up = conv_bn(f"{name}_cat", 1792, 1, relu=False)
            gb.add_vertex(f"{name}_scale", ScaleVertex(scale=scale), up)
            gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), inp, f"{name}_scale")
            gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_add")
            return f"{name}_relu"

        # stem
        x = conv_bn("input", 32, 3, stride=(2, 2))
        x = conv_bn(x, 32, 3, pad="VALID")
        x = conv_bn(x, 64, 3)
        gb.add_layer("stem_pool", SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), x)
        x = conv_bn("stem_pool", 80, 1)
        x = conv_bn(x, 192, 3, pad="VALID")
        x = conv_bn(x, 256, 3, stride=(2, 2))
        for _ in range(5):
            x = block35(x)
        # reduction-A → 896 channels
        ra0 = conv_bn(x, 384, 3, stride=(2, 2), pad="VALID")
        ra1 = conv_bn(conv_bn(conv_bn(x, 192, 1), 192, 3), 256, 3,
                      stride=(2, 2), pad="VALID")
        gb.add_layer("redA_pool", SubsamplingLayer(kernel_size=(3, 3),
                                                   stride=(2, 2)), x)
        gb.add_vertex("redA", MergeVertex(), ra0, ra1, "redA_pool")
        x = "redA"
        for _ in range(10):
            x = block17(x)
        # reduction-B → 1792 channels
        rb0 = conv_bn(conv_bn(x, 256, 1), 384, 3, stride=(2, 2), pad="VALID")
        rb1 = conv_bn(conv_bn(x, 256, 1), 256, 3, stride=(2, 2), pad="VALID")
        rb2 = conv_bn(conv_bn(conv_bn(x, 256, 1), 256, 3), 256, 3,
                      stride=(2, 2), pad="VALID")
        gb.add_layer("redB_pool", SubsamplingLayer(kernel_size=(3, 3),
                                                   stride=(2, 2)), x)
        gb.add_vertex("redB", MergeVertex(), rb0, rb1, rb2, "redB_pool")
        x = "redB"
        for _ in range(5):
            x = block8(x)
        gb.add_layer("gap", GlobalPoolingLayer(), x)
        gb.add_layer("embedding", DenseLayer(n_in=1792, n_out=self.embedding_size), "gap")
        gb.add_vertex("embed_norm", L2NormalizeVertex(), "embedding")
        gb.add_layer("output", OutputLayer(n_in=self.embedding_size,
                                           n_out=self.num_classes), "embed_norm")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()


@dataclasses.dataclass
class FaceNetNN4Small2(ZooModel):
    """zoo/model/FaceNetNN4Small2.java — the OpenFace nn4.small2 inception
    face-embedding net (path-cite, mount empty): 7×7/2 stem, inception-2
    3a/3b/3c/4a/4e/5a/5b mixed modules (1×1 + reduced 3×3 + reduced 5×5 +
    pool-projection branches), avg pool, 128-d L2-normalized embedding,
    softmax head for classifier training."""

    input_shape: Tuple[int, int, int] = (96, 96, 3)
    embedding_size: int = 128

    def conf(self):
        from deeplearning4j_tpu.nn.vertices import L2NormalizeVertex

        h, w, c = self.input_shape
        gb = self._builder().graph_builder().add_inputs("input")
        uid = [0]

        def conv_bn(inp, n_out, k, stride=(1, 1), pad="SAME"):
            uid[0] += 1
            name = f"f{uid[0]}"
            gb.add_layer(f"{name}_c", ConvolutionLayer(
                n_out=n_out, kernel_size=(k, k) if isinstance(k, int) else k,
                stride=stride, padding=pad, has_bias=False), inp)
            gb.add_layer(f"{name}_b", BatchNormalization(), f"{name}_c")
            gb.add_layer(f"{name}_r", ActivationLayer(activation="relu"),
                         f"{name}_b")
            return f"{name}_r"

        def inception(inp, c1, r3, c3, r5, c5, pool_proj, stride=(1, 1)):
            """nn4.small2 mixed module; any branch with 0 channels is
            omitted (the reference's 3c/4e reduction modules)."""
            uid[0] += 1
            name = f"inc{uid[0]}"
            branches = []
            if c1:
                branches.append(conv_bn(inp, c1, 1))
            if c3:
                branches.append(conv_bn(conv_bn(inp, r3, 1), c3, 3,
                                        stride=stride))
            if c5:
                branches.append(conv_bn(conv_bn(inp, r5, 1), c5, 5,
                                        stride=stride))
            pname = f"{name}_pool"
            gb.add_layer(pname, SubsamplingLayer(
                kernel_size=(3, 3), stride=stride, padding="SAME"), inp)
            branches.append(conv_bn(pname, pool_proj, 1)
                            if pool_proj else pname)
            gb.add_vertex(name, MergeVertex(), *branches)
            return name

        x = conv_bn("input", 64, 7, stride=(2, 2))
        gb.add_layer("p1", SubsamplingLayer(kernel_size=(3, 3),
                                            stride=(2, 2), padding="SAME"), x)
        x = conv_bn("p1", 64, 1)
        x = conv_bn(x, 192, 3)
        gb.add_layer("p2", SubsamplingLayer(kernel_size=(3, 3),
                                            stride=(2, 2), padding="SAME"), x)
        # nn4.small2 channel table
        x = inception("p2", 64, 96, 128, 16, 32, 32)       # 3a
        x = inception(x, 64, 96, 128, 32, 64, 64)          # 3b
        x = inception(x, 0, 128, 256, 32, 64, 0,
                      stride=(2, 2))                       # 3c (reduction)
        x = inception(x, 256, 96, 192, 32, 64, 128)        # 4a
        x = inception(x, 0, 160, 256, 64, 128, 0,
                      stride=(2, 2))                       # 4e (reduction)
        x = inception(x, 256, 96, 384, 0, 0, 96)           # 5a
        x = inception(x, 256, 96, 384, 0, 0, 96)           # 5b
        gb.add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), x)
        gb.add_layer("embedding", DenseLayer(
            n_in=736, n_out=self.embedding_size), "gap")
        gb.add_vertex("embed_norm", L2NormalizeVertex(), "embedding")
        gb.add_layer("output", OutputLayer(n_in=self.embedding_size,
                                           n_out=self.num_classes),
                     "embed_norm")
        gb.set_outputs("output")
        gb.set_input_types(InputType.convolutional(h, w, c))
        return gb.build()
