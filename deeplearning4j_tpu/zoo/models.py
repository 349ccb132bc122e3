"""The 13 zoo architectures.

Reference: ``deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/model/``
(AlexNet, Darknet19, FaceNetNN4Small2, GoogLeNet, InceptionResNetV1, LeNet,
ResNet50, SimpleCNN, TextGenerationLSTM, TinyYOLO, VGG16, VGG19, YOLO2).
Configs are built on the TPU-native builder DSL; data layout is NHWC (the
TPU-friendly layout) rather than the reference's NCHW, and convs fold their
batch-norms' scale at inference via XLA fusion rather than cuDNN algo modes.

``ModelMetaData.input_shape`` keeps DL4J's CHW ordering for documentation
parity; actual arrays are NHWC.
"""

from __future__ import annotations

from typing import Tuple

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    ActivationLayer,
    BatchNormalizationLayer,
    ConvolutionLayer,
    DenseLayer,
    DropoutLayer,
    GlobalPoolingLayer,
    GravesLSTMLayer,
    LocalResponseNormalizationLayer,
    LossLayer,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
from deeplearning4j_tpu.nn.layers.output import CenterLossOutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, AdaDelta, Nesterovs
from deeplearning4j_tpu.nn.vertices import L2NormalizeVertex, MergeVertex
from deeplearning4j_tpu.zoo.helpers import (
    conv_bn_act,
    darknet_block,
    inception_module,
    inception_resnet_block_a,
    inception_resnet_block_b,
    inception_resnet_block_c,
    resnet_conv_block,
    resnet_identity_block,
)
from deeplearning4j_tpu.zoo.zoo_model import (
    ModelMetaData,
    PretrainedType,
    ZooModel,
    register_zoo_model,
)


@register_zoo_model
class LeNet(ZooModel):
    """LeNet-5-style CNN (``zoo/model/LeNet.java``: 20/50 conv, 500 dense)."""

    # the reference's published artifact registry (LeNet.java:58-70); these
    # DL4J ModelSerializer zips restore through our DL4J reader when fetched
    PRETRAINED_URLS = {PretrainedType.MNIST:
                       "http://blob.deeplearning4j.org/models/lenet_dl4j_mnist_inference.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.MNIST: 1906861161}

    def __init__(self, num_labels: int = 10, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (1, 28, 28)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        return (NeuralNetConfiguration.builder().seed(self.seed)
                .activation("identity").weight_init("xavier")
                .updater(AdaDelta()).list()
                .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5), stride=(1, 1),
                                        convolution_mode="same", activation="identity"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5), stride=(1, 1),
                                        convolution_mode="same", activation="identity"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
                .layer(DenseLayer(n_out=500, activation="relu"))
                .layer(OutputLayer(n_out=self.num_labels, loss="mcxent", activation="softmax"))
                .set_input_type(InputType.convolutional(h, w, c)).build())


@register_zoo_model
class SimpleCNN(ZooModel):
    """Conv/BN/avg-pool stack ending in a fully convolutional softmax head
    (``zoo/model/SimpleCNN.java:77-125``)."""

    def __init__(self, num_labels: int = 10, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 48, 48)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        b = (NeuralNetConfiguration.builder().seed(self.seed)
             .activation("relu").weight_init("relu").updater(AdaDelta()).list())
        # block 1: two 7x7 convs @16
        b.layer(ConvolutionLayer(n_out=16, kernel_size=(7, 7), convolution_mode="same"))
        b.layer(BatchNormalizationLayer())
        b.layer(ConvolutionLayer(n_out=16, kernel_size=(7, 7), convolution_mode="same"))
        b.layer(BatchNormalizationLayer())
        b.layer(ActivationLayer(activation="relu"))
        b.layer(SubsamplingLayer(pooling_type="avg", kernel_size=(2, 2), stride=(2, 2)))
        b.layer(DropoutLayer(dropout=0.5))
        for n in (32, 64, 128):
            k = 5 if n == 32 else 3
            b.layer(ConvolutionLayer(n_out=n, kernel_size=(k, k), convolution_mode="same"))
            b.layer(BatchNormalizationLayer())
            b.layer(ConvolutionLayer(n_out=n, kernel_size=(k, k), convolution_mode="same"))
            b.layer(BatchNormalizationLayer())
            b.layer(ActivationLayer(activation="relu"))
            b.layer(SubsamplingLayer(pooling_type="avg", kernel_size=(2, 2), stride=(2, 2)))
            b.layer(DropoutLayer(dropout=0.5))
        b.layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), convolution_mode="same"))
        b.layer(BatchNormalizationLayer())
        b.layer(ConvolutionLayer(n_out=self.num_labels, kernel_size=(3, 3),
                                 convolution_mode="same", activation="identity"))
        b.layer(GlobalPoolingLayer(pooling_type="avg"))
        b.layer(LossLayer(loss="mcxent", activation="softmax"))
        return b.set_input_type(InputType.convolutional(h, w, c)).build()


@register_zoo_model
class AlexNet(ZooModel):
    """AlexNet (one-tower variant, ``zoo/model/AlexNet.java``)."""

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        from deeplearning4j_tpu.nn.weights import Distribution
        return (NeuralNetConfiguration.builder().seed(self.seed)
                .activation("relu")
                .weight_init("distribution", Distribution("normal", 0.0, 0.005))
                .updater(Nesterovs(1e-2, 0.9)).l2(5e-4).list()
                .layer(ConvolutionLayer(n_out=64, kernel_size=(11, 11), stride=(4, 4),
                                        padding=(3, 3)))
                .layer(LocalResponseNormalizationLayer())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=192, kernel_size=(5, 5), convolution_mode="same"))
                .layer(LocalResponseNormalizationLayer())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), convolution_mode="same"))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), convolution_mode="same"))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), convolution_mode="same"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
                .layer(DenseLayer(n_out=4096, dropout=0.5))
                .layer(DenseLayer(n_out=4096, dropout=0.5))
                .layer(OutputLayer(n_out=self.num_labels, loss="mcxent", activation="softmax"))
                .set_input_type(InputType.convolutional(h, w, c)).build())


def _vgg_conf(blocks, num_labels, seed, input_shape):
    c, h, w = input_shape
    b = (NeuralNetConfiguration.builder().seed(seed)
         .activation("relu").weight_init("xavier").updater(Nesterovs(1e-2, 0.9)).list())
    for n_convs, n_out in blocks:
        for _ in range(n_convs):
            b.layer(ConvolutionLayer(n_out=n_out, kernel_size=(3, 3), convolution_mode="same"))
        b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
    b.layer(DenseLayer(n_out=4096, dropout=0.5))
    b.layer(DenseLayer(n_out=4096, dropout=0.5))
    b.layer(OutputLayer(n_out=num_labels, loss="mcxent", activation="softmax"))
    return b.set_input_type(InputType.convolutional(h, w, c)).build()


@register_zoo_model
class VGG16(ZooModel):
    """VGG-16 (``zoo/model/VGG16.java``; Simonyan & Zisserman 2014)."""

    # published artifacts (VGG16.java:58-79)
    PRETRAINED_URLS = {
        PretrainedType.IMAGENET: "http://blob.deeplearning4j.org/models/vgg16_dl4j_inference.zip",
        PretrainedType.CIFAR10: "http://blob.deeplearning4j.org/models/vgg16_dl4j_cifar10_inference.v1.zip",
        PretrainedType.VGGFACE: "http://blob.deeplearning4j.org/models/vgg16_dl4j_vggface_inference.v1.zip",
    }
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 3501732770,
                            PretrainedType.CIFAR10: 2192260131,
                            PretrainedType.VGGFACE: 2706403553}

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        return _vgg_conf([(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)],
                         self.num_labels, self.seed, self.input_shape)


@register_zoo_model
class VGG19(ZooModel):
    """VGG-19 (``zoo/model/VGG19.java``)."""

    PRETRAINED_URLS = {PretrainedType.IMAGENET:
                       "http://blob.deeplearning4j.org/models/vgg19_dl4j_inference.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 2782932419}

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        return _vgg_conf([(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)],
                         self.num_labels, self.seed, self.input_shape)


@register_zoo_model
class Darknet19(ZooModel):
    """Darknet-19 classifier (``zoo/model/Darknet19.java`` via DarknetHelper).

    The published artifact depends on the input resolution
    (``Darknet19.java:60-76``) — :meth:`pretrained_url` and
    :meth:`pretrained_checksum` override the registries accordingly."""

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def _artifact_name(self, pretrained_type):
        # 224 and 448 weights are different artifacts (different URLs and
        # checksums) — they must not share one cache slot
        if self.input_shape[1] == 448 and self.input_shape[2] == 448:
            return f"darknet19_448_{pretrained_type}.zip"
        return f"darknet19_{pretrained_type}.zip"

    def pretrained_url(self, pretrained_type):
        if pretrained_type != PretrainedType.IMAGENET:
            return None
        if self.input_shape[1] == 448 and self.input_shape[2] == 448:
            return "http://blob.deeplearning4j.org/models/darknet19_448_dl4j_inference.v1.zip"
        return "http://blob.deeplearning4j.org/models/darknet19_dl4j_inference.v1.zip"

    def pretrained_checksum(self, pretrained_type):
        if pretrained_type != PretrainedType.IMAGENET:
            return 0
        if self.input_shape[1] == 448 and self.input_shape[2] == 448:
            return 870575230
        return 3952910425

    def conf(self):
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Nesterovs(1e-3, 0.9)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        x = darknet_block(g, 1, "input", 32, pool=2)
        x = darknet_block(g, 2, x, 64, pool=2)
        x = darknet_block(g, 3, x, 128)
        x = darknet_block(g, 4, x, 64, filter_size=1)
        x = darknet_block(g, 5, x, 128, pool=2)
        x = darknet_block(g, 6, x, 256)
        x = darknet_block(g, 7, x, 128, filter_size=1)
        x = darknet_block(g, 8, x, 256, pool=2)
        x = darknet_block(g, 9, x, 512)
        x = darknet_block(g, 10, x, 256, filter_size=1)
        x = darknet_block(g, 11, x, 512)
        x = darknet_block(g, 12, x, 256, filter_size=1)
        x = darknet_block(g, 13, x, 512, pool=2)
        x = darknet_block(g, 14, x, 1024)
        x = darknet_block(g, 15, x, 512, filter_size=1)
        x = darknet_block(g, 16, x, 1024)
        x = darknet_block(g, 17, x, 512, filter_size=1)
        x = darknet_block(g, 18, x, 1024)
        g.add_layer("convolution2d_19",
                    ConvolutionLayer(n_out=self.num_labels, kernel_size=(1, 1),
                                     convolution_mode="same", activation="identity"), x)
        g.add_layer("globalpooling", GlobalPoolingLayer(pooling_type="avg"),
                    "convolution2d_19")
        g.add_layer("loss", LossLayer(loss="mcxent", activation="softmax"),
                    "globalpooling")
        return g.set_outputs("loss").build()


# Anchor priors from the reference (TinyYOLO.java / YOLO2.java), grid units.
TINY_YOLO_ANCHORS = ((1.08, 1.19), (3.42, 4.41), (6.63, 11.38), (9.42, 5.11), (16.62, 10.52))
YOLO2_ANCHORS = ((0.57273, 0.677385), (1.87446, 2.06253), (3.33843, 5.47434),
                 (7.88282, 3.52778), (9.77052, 9.16828))


@register_zoo_model
class TinyYOLO(ZooModel):
    """Tiny YOLOv2 detector (``zoo/model/TinyYOLO.java``)."""

    PRETRAINED_URLS = {PretrainedType.IMAGENET:
                       "http://blob.deeplearning4j.org/models/tiny-yolo-voc_dl4j_inference.v1.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 2004171617}

    def __init__(self, num_labels: int = 20, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 416, 416)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        nb = len(TINY_YOLO_ANCHORS)
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(1e-3)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        x = darknet_block(g, 1, "input", 16, pool=2)
        x = darknet_block(g, 2, x, 32, pool=2)
        x = darknet_block(g, 3, x, 64, pool=2)
        x = darknet_block(g, 4, x, 128, pool=2)
        x = darknet_block(g, 5, x, 256, pool=2)
        x = darknet_block(g, 6, x, 512, pool=2, pool_stride=1)
        x = darknet_block(g, 7, x, 1024)
        x = darknet_block(g, 8, x, 1024)
        g.add_layer("convolution2d_9",
                    ConvolutionLayer(n_out=nb * (5 + self.num_labels), kernel_size=(1, 1),
                                     convolution_mode="same", activation="identity"), x)
        g.add_layer("outputs", Yolo2OutputLayer(boxes=TINY_YOLO_ANCHORS,
                                                n_classes=self.num_labels),
                    "convolution2d_9")
        return g.set_outputs("outputs").build()


@register_zoo_model
class YOLO2(ZooModel):
    """YOLOv2 with Darknet-19 backbone + passthrough reorg
    (``zoo/model/YOLO2.java``: SpaceToDepth passthrough merged before head)."""

    PRETRAINED_URLS = {PretrainedType.IMAGENET:
                       "http://blob.deeplearning4j.org/models/yolo2_dl4j_inference.v1.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 1357637732}

    def __init__(self, num_labels: int = 80, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 608, 608)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers.conv import SpaceToDepthLayer
        c, h, w = self.input_shape
        nb = len(YOLO2_ANCHORS)
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(1e-3)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        x = darknet_block(g, 1, "input", 32, pool=2)
        x = darknet_block(g, 2, x, 64, pool=2)
        x = darknet_block(g, 3, x, 128)
        x = darknet_block(g, 4, x, 64, filter_size=1)
        x = darknet_block(g, 5, x, 128, pool=2)
        x = darknet_block(g, 6, x, 256)
        x = darknet_block(g, 7, x, 128, filter_size=1)
        x = darknet_block(g, 8, x, 256, pool=2)
        x = darknet_block(g, 9, x, 512)
        x = darknet_block(g, 10, x, 256, filter_size=1)
        x = darknet_block(g, 11, x, 512)
        x = darknet_block(g, 12, x, 256, filter_size=1)
        passthrough = darknet_block(g, 13, x, 512)  # 1/16 resolution feature map
        g.add_layer("maxpooling2d_13",
                    SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)),
                    passthrough)
        x = darknet_block(g, 14, "maxpooling2d_13", 1024)
        x = darknet_block(g, 15, x, 512, filter_size=1)
        x = darknet_block(g, 16, x, 1024)
        x = darknet_block(g, 17, x, 512, filter_size=1)
        x = darknet_block(g, 18, x, 1024)
        x = darknet_block(g, 19, x, 1024)
        x = darknet_block(g, 20, x, 1024)
        # passthrough: reorg 1/16 map to 1/32 and concat with the deep map
        g.add_layer("reorg", SpaceToDepthLayer(block_size=2), passthrough)
        g.add_vertex("concat", MergeVertex(), "reorg", x)
        x = darknet_block(g, 21, "concat", 1024)
        g.add_layer("convolution2d_22",
                    ConvolutionLayer(n_out=nb * (5 + self.num_labels), kernel_size=(1, 1),
                                     convolution_mode="same", activation="identity"), x)
        g.add_layer("outputs", Yolo2OutputLayer(boxes=YOLO2_ANCHORS,
                                                n_classes=self.num_labels),
                    "convolution2d_22")
        return g.set_outputs("outputs").build()


@register_zoo_model
class ResNet50(ZooModel):
    """ResNet-50 (``zoo/model/ResNet50.java:89-216``): 7x7 stem then
    [3,4,6,3] bottleneck stages."""

    PRETRAINED_URLS = {PretrainedType.IMAGENET:
                       "http://blob.deeplearning4j.org/models/resnet50_dl4j_inference.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 1982516793}

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .activation("identity").weight_init("xavier")
             .updater(Nesterovs(1e-2, 0.9)).l1(1e-7).l2(5e-5).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        g.add_layer("stem-zero", ZeroPaddingLayer(padding=(3, 3)), "input")
        g.add_layer("stem-cnn1",
                    ConvolutionLayer(n_out=64, kernel_size=(7, 7), stride=(2, 2),
                                     activation="identity", has_bias=False,
                                     space_to_depth_stem=True), "stem-zero")
        g.add_layer("stem-batch1", BatchNormalizationLayer(activation="identity"), "stem-cnn1")
        g.add_layer("stem-act1", ActivationLayer(activation="relu"), "stem-batch1")
        g.add_layer("stem-maxpool1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)),
                    "stem-act1")
        # canonical ResNet-50 stride-1 projection at stage 2 (the stem maxpool
        # already downsampled); the reference's ResNet50.java:194 passes {2,2}
        # here, a known deviation that breaks pretrained-weight compatibility
        x = resnet_conv_block(g, (3, 3), (64, 64, 256), "2", "a", "stem-maxpool1",
                              stride=(1, 1))
        x = resnet_identity_block(g, (3, 3), (64, 64, 256), "2", "b", x)
        x = resnet_identity_block(g, (3, 3), (64, 64, 256), "2", "c", x)
        x = resnet_conv_block(g, (3, 3), (128, 128, 512), "3", "a", x)
        for blk in "bcd":
            x = resnet_identity_block(g, (3, 3), (128, 128, 512), "3", blk, x)
        x = resnet_conv_block(g, (3, 3), (256, 256, 1024), "4", "a", x)
        for blk in "bcdef":
            x = resnet_identity_block(g, (3, 3), (256, 256, 1024), "4", blk, x)
        x = resnet_conv_block(g, (3, 3), (512, 512, 2048), "5", "a", x)
        for blk in "bc":
            x = resnet_identity_block(g, (3, 3), (512, 512, 2048), "5", blk, x)
        g.add_layer("avgpool",
                    SubsamplingLayer(pooling_type="avg", kernel_size=(3, 3), stride=(1, 1),
                                     convolution_mode="same"), x)
        g.add_layer("globalpool", GlobalPoolingLayer(pooling_type="avg"), "avgpool")
        g.add_layer("fc1000", OutputLayer(n_out=self.num_labels, loss="mcxent",
                                          activation="softmax"), "globalpool")
        return g.set_outputs("fc1000").build()


@register_zoo_model
class GoogLeNet(ZooModel):
    """GoogLeNet / Inception-v1 (``zoo/model/GoogLeNet.java``)."""

    PRETRAINED_URLS = {PretrainedType.IMAGENET:
                       "http://blob.deeplearning4j.org/models/googlenet_dl4j_inference.zip"}
    PRETRAINED_CHECKSUMS = {PretrainedType.IMAGENET: 3337733202}

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 224, 224)):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .activation("relu").weight_init("xavier")
             .updater(Nesterovs(1e-2, 0.9)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        g.add_layer("cnn1", ConvolutionLayer(n_out=64, kernel_size=(7, 7), stride=(2, 2),
                                             convolution_mode="same"), "input")
        g.add_layer("max1", SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                             stride=(2, 2), convolution_mode="same"), "cnn1")
        g.add_layer("lrn1", LocalResponseNormalizationLayer(), "max1")
        g.add_layer("cnn2", ConvolutionLayer(n_out=64, kernel_size=(1, 1)), "lrn1")
        g.add_layer("cnn3", ConvolutionLayer(n_out=192, kernel_size=(3, 3),
                                             convolution_mode="same"), "cnn2")
        g.add_layer("lrn2", LocalResponseNormalizationLayer(), "cnn3")
        g.add_layer("max2", SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                             stride=(2, 2), convolution_mode="same"), "lrn2")
        x = inception_module(g, "3a", "max2", 64, 96, 128, 16, 32, 32)
        x = inception_module(g, "3b", x, 128, 128, 192, 32, 96, 64)
        g.add_layer("max3", SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                             stride=(2, 2), convolution_mode="same"), x)
        x = inception_module(g, "4a", "max3", 192, 96, 208, 16, 48, 64)
        x = inception_module(g, "4b", x, 160, 112, 224, 24, 64, 64)
        x = inception_module(g, "4c", x, 128, 128, 256, 24, 64, 64)
        x = inception_module(g, "4d", x, 112, 144, 288, 32, 64, 64)
        x = inception_module(g, "4e", x, 256, 160, 320, 32, 128, 128)
        g.add_layer("max4", SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                             stride=(2, 2), convolution_mode="same"), x)
        x = inception_module(g, "5a", "max4", 256, 160, 320, 32, 128, 128)
        x = inception_module(g, "5b", x, 384, 192, 384, 48, 128, 128)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("dropout", DropoutLayer(dropout=0.4), "avgpool")
        g.add_layer("output", OutputLayer(n_out=self.num_labels, loss="mcxent",
                                          activation="softmax"), "dropout")
        return g.set_outputs("output").build()


@register_zoo_model
class InceptionResNetV1(ZooModel):
    """Inception-ResNet-v1 with center-loss embedding head
    (``zoo/model/InceptionResNetV1.java``: stem → 5×A → reduction →
    10×B → reduction → 5×C → bottleneck → center-loss output)."""

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 160, 160),
                 embedding_size: int = 128):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape
        self.embedding_size = embedding_size

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def _stem(self, g, inp):
        x = conv_bn_act(g, "stem-1", inp, 32, (3, 3), (2, 2))
        x = conv_bn_act(g, "stem-2", x, 32, (3, 3))
        x = conv_bn_act(g, "stem-3", x, 64, (3, 3))
        g.add_layer("stem-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        x = conv_bn_act(g, "stem-4", "stem-pool", 80, (1, 1))
        x = conv_bn_act(g, "stem-5", x, 192, (3, 3))
        x = conv_bn_act(g, "stem-6", x, 256, (3, 3), (2, 2))
        return x

    def conf(self):
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .activation("relu").weight_init("relu")
             .updater(Adam(1e-3)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        x = self._stem(g, "input")
        for i in range(5):
            x = inception_resnet_block_a(g, f"block35-{i}", x, 0.17)
        # reduction A: 256 → 896 channels, spatial /2
        ra_b1 = conv_bn_act(g, "redA-b1", x, 384, (3, 3), (2, 2))
        ra_b2a = conv_bn_act(g, "redA-b2a", x, 192, (1, 1))
        ra_b2b = conv_bn_act(g, "redA-b2b", ra_b2a, 192, (3, 3))
        ra_b2 = conv_bn_act(g, "redA-b2c", ra_b2b, 256, (3, 3), (2, 2))
        g.add_layer("redA-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        g.add_vertex("redA", MergeVertex(), ra_b1, ra_b2, "redA-pool")
        x = "redA"
        for i in range(10):
            x = inception_resnet_block_b(g, f"block17-{i}", x, 0.10)
        # reduction B: 896 → 1792, spatial /2
        rb_b1a = conv_bn_act(g, "redB-b1a", x, 256, (1, 1))
        rb_b1 = conv_bn_act(g, "redB-b1b", rb_b1a, 384, (3, 3), (2, 2))
        rb_b2a = conv_bn_act(g, "redB-b2a", x, 256, (1, 1))
        rb_b2 = conv_bn_act(g, "redB-b2b", rb_b2a, 256, (3, 3), (2, 2))
        rb_b3a = conv_bn_act(g, "redB-b3a", x, 256, (1, 1))
        rb_b3b = conv_bn_act(g, "redB-b3b", rb_b3a, 256, (3, 3))
        rb_b3 = conv_bn_act(g, "redB-b3c", rb_b3b, 256, (3, 3), (2, 2))
        g.add_layer("redB-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        g.add_vertex("redB", MergeVertex(), rb_b1, rb_b2, rb_b3, "redB-pool")
        x = "redB"
        for i in range(5):
            x = inception_resnet_block_c(g, f"block8-{i}", x, 0.20)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("dropout", DropoutLayer(dropout=0.8), "avgpool")
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"), "dropout")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("lossLayer",
                    CenterLossOutputLayer(n_out=self.num_labels, loss="mcxent",
                                          activation="softmax", alpha=0.9, lambda_=1e-4),
                    "embeddings")
        return g.set_outputs("lossLayer").build()


@register_zoo_model
class FaceNetNN4Small2(ZooModel):
    """FaceNet NN4.small2 embedding net (``zoo/model/FaceNetNN4Small2.java``):
    inception-style trunk → 128-d L2-normalized embedding → center loss."""

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = (3, 96, 96),
                 embedding_size: int = 128):
        super().__init__(num_labels, seed)
        self.input_shape = input_shape
        self.embedding_size = embedding_size

    def meta_data(self):
        return ModelMetaData((self.input_shape,), 1, "cnn")

    def conf(self):
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .activation("relu").weight_init("relu")
             .updater(Adam(0.1)).graph_builder()
             .add_inputs("input").set_input_types(InputType.convolutional(h, w, c)))
        x = conv_bn_act(g, "stem-cnn1", "input", 64, (7, 7), (2, 2))
        g.add_layer("stem-pool1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        x = conv_bn_act(g, "inception-2", "stem-pool1", 64, (1, 1))
        x = conv_bn_act(g, "inception-3", x, 192, (3, 3))
        g.add_layer("stem-pool2",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        x = inception_module(g, "3a", "stem-pool2", 64, 96, 128, 16, 32, 32)
        x = inception_module(g, "3b", x, 64, 96, 128, 32, 64, 64)
        g.add_layer("pool3",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        x = inception_module(g, "4a", "pool3", 256, 96, 192, 32, 64, 128)
        x = inception_module(g, "4e", x, 160, 128, 256, 32, 64, 128)
        g.add_layer("pool4",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2),
                                     convolution_mode="same"), x)
        x = inception_module(g, "5a", "pool4", 256, 96, 384, 24, 64, 96)
        x = inception_module(g, "5b", x, 256, 96, 384, 24, 64, 96)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"), "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("lossLayer",
                    CenterLossOutputLayer(n_out=self.num_labels, loss="mcxent",
                                          activation="softmax", alpha=0.9, lambda_=1e-4),
                    "embeddings")
        return g.set_outputs("lossLayer").build()


@register_zoo_model
class TextGenerationLSTM(ZooModel):
    """Char-level text generation LSTM (``zoo/model/TextGenerationLSTM.java:81-86``:
    2× GravesLSTM(256) → RnnOutputLayer MCXENT)."""

    def __init__(self, num_labels: int = 26, seed: int = 123, max_length: int = 40):
        super().__init__(num_labels, seed)
        self.max_length = max_length

    def meta_data(self):
        return ModelMetaData(((self.max_length, self.num_labels),), 1, "rnn")

    def conf(self):
        return (NeuralNetConfiguration.builder().seed(self.seed)
                .weight_init("xavier").updater("rmsprop")
                .l2(0.001)
                .gradient_normalization("clip_elementwise_absolute_value", 10.0).list()
                .layer(GravesLSTMLayer(n_in=self.num_labels, n_out=256, activation="tanh"))
                .layer(GravesLSTMLayer(n_out=256, activation="tanh"))
                .layer(RnnOutputLayer(n_out=self.num_labels, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(self.num_labels, self.max_length))
                .build())


def transformer_decoder_block(g, name: str, src: str, d_model: int,
                              n_heads: int, d_ff: int, max_len: int,
                              attn_dropout: float = 0.0) -> str:
    """One pre-LN causal decoder block (GPT-style): LN → causal self-attention
    → residual, LN → position-wise FFN → residual. Pre-LN because it trains
    stably without warmup — the modern decoder default. Returns the output
    vertex name."""
    from deeplearning4j_tpu.nn.layers import (
        CausalSelfAttentionLayer,
        LayerNormalizationLayer,
    )
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex

    g.add_layer(f"{name}-ln1", LayerNormalizationLayer(), src)
    g.add_layer(f"{name}-att",
                CausalSelfAttentionLayer(n_heads=n_heads,
                                         head_size=d_model // n_heads,
                                         project_input=True,
                                         max_cache=max_len,
                                         attn_dropout=attn_dropout),
                f"{name}-ln1")
    g.add_vertex(f"{name}-res1", ElementWiseVertex(op="add"),
                 src, f"{name}-att")
    g.add_layer(f"{name}-ln2", LayerNormalizationLayer(), f"{name}-res1")
    g.add_layer(f"{name}-ff1", DenseLayer(n_in=d_model, n_out=d_ff,
                                          activation="gelu"), f"{name}-ln2")
    g.add_layer(f"{name}-ff2", DenseLayer(n_in=d_ff, n_out=d_model,
                                          activation="identity"),
                f"{name}-ff1")
    g.add_vertex(f"{name}-res2", ElementWiseVertex(op="add"),
                 f"{name}-res1", f"{name}-ff2")
    return f"{name}-res2"


@register_zoo_model
class TransformerLM(ZooModel):
    """GPT-style causal-decoder language model — the attention-era successor
    of ``TextGenerationLSTM`` (``zoo/model/TextGenerationLSTM.java``): token
    ids [N,T] → embedding + learned positions → n pre-LN causal decoder
    blocks → final LayerNorm → per-timestep softmax over the vocabulary
    (RnnOutputLayer, MCXENT). Labels are the inputs shifted left by one, as
    int32 class ids [N,T] (see :func:`lm_labels`): the loss picks each
    target by its id, and no [N,T,V] one-hot exists on host or device.

    Generation uses the network's stateful ``rnn_time_step`` path: every
    causal attention layer carries a fixed-capacity KV cache, so sampling N
    tokens is N jitted single-token steps, not N quadratic re-forwards.
    Defaults are GPT-2-small shape (12L / 768 / 12H / 3072).
    """

    def __init__(self, num_labels: int = 0, seed: int = 123,
                 vocab_size: int = 50257, max_length: int = 1024,
                 n_layers: int = 12, d_model: int = 768, n_heads: int = 12,
                 d_ff: int = 3072, attn_dropout: float = 0.0):
        # for an LM the label space IS the vocabulary: num_labels, when
        # given (e.g. via ModelSelector), overrides vocab_size — the same
        # convention as TextGenerationLSTM(num_labels=vocab)
        vocab_size = num_labels or vocab_size
        super().__init__(vocab_size, seed)
        self.vocab_size = vocab_size
        self.max_length = max_length
        self.n_layers = n_layers
        self.d_model = d_model
        self.n_heads = n_heads
        self.d_ff = d_ff
        self.attn_dropout = attn_dropout

    def meta_data(self):
        return ModelMetaData(((self.max_length,),), 1, "rnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer,
            LayerNormalizationLayer,
            PositionalEmbeddingLayer,
        )

        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(3e-4)).graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(1, self.max_length)))
        g.add_layer("embed",
                    EmbeddingSequenceLayer(n_in=self.vocab_size,
                                           n_out=self.d_model), "tokens")
        g.add_layer("pos", PositionalEmbeddingLayer(n_in=self.d_model,
                                                    max_len=self.max_length),
                    "embed")
        src = "pos"
        for i in range(self.n_layers):
            src = transformer_decoder_block(g, f"block{i}", src,
                                            self.d_model, self.n_heads,
                                            self.d_ff, self.max_length,
                                            self.attn_dropout)
        g.add_layer("ln_f", LayerNormalizationLayer(), src)
        g.add_layer("out", RnnOutputLayer(n_in=self.d_model,
                                          n_out=self.vocab_size,
                                          activation="softmax", loss="mcxent"),
                    "ln_f")
        g.set_outputs("out")
        return g.build()


def lm_labels(tokens, vocab_size: int):
    """Next-token targets for causal LM training, as int32 class ids [N,T]:
    labels[t] = tokens[t+1]; the last step repeats the last token (give it a
    [N,T] label mask with 0 in the final column to drop it from the loss).
    An id outside ``[0, vocab_size)`` raises here, on the host: the compiled
    loss would clamp it in silence."""
    import numpy as np
    ids = np.asarray(tokens).astype(np.int64)
    if ids.size and not (0 <= ids.min() and ids.max() < vocab_size):
        raise ValueError(
            f"token ids must lie in [0, {vocab_size}); got "
            f"[{ids.min()}, {ids.max()}]")
    return np.concatenate([ids[:, 1:], ids[:, -1:]], axis=1).astype(np.int32)


def generate(net, prompt_ids, n_new_tokens: int, temperature: float = 0.0,
             seed: int = 0):
    """Autoregressive sampling from a trained :class:`TransformerLM` network.

    Feeds the whole prompt through the stateful KV-cached path once, then
    samples one token per jitted step (n_new_tokens - 1 incremental steps
    total — the last sampled token is not fed back). ``temperature=0`` is
    greedy argmax. Returns [N, n_new_tokens] generated ids.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    ids, empty = _prep_prompt(net, prompt_ids, n_new_tokens)
    if empty is not None:
        return empty
    net.rnn_clear_previous_state()
    # [N,T,1] so rnn_time_step keeps the time axis (ids are "features")
    probs = np.asarray(net.rnn_time_step(ids[:, :, None].astype(np.float32)))
    out = []
    for i in range(n_new_tokens):
        p_last = probs[:, -1, :] if probs.ndim == 3 else probs
        if temperature and temperature > 0:
            logits = np.log(np.maximum(p_last, 1e-20)) / temperature
            z = np.exp(logits - logits.max(axis=-1, keepdims=True))
            p = (z / z.sum(axis=-1, keepdims=True)).astype(np.float64)
            p /= p.sum(axis=-1, keepdims=True)  # exact for rng.choice's check
            nxt = np.array([rng.choice(p.shape[-1], p=row) for row in p])
        else:
            nxt = np.argmax(p_last, axis=-1)
        out.append(nxt)
        if i < n_new_tokens - 1:
            probs = np.asarray(
                net.rnn_time_step(nxt[:, None, None].astype(np.float32)))
    return np.stack(out, axis=1)


def generate_on_device(net, prompt_ids, n_new_tokens: int,
                       temperature: float = 0.0, seed: int = 0,
                       top_k: int = 0, top_p: float = 0.0):
    """Autoregressive sampling compiled to ONE device executable: prompt
    prefill fills every KV cache, then a ``lax.scan`` decodes one token per
    step with on-device argmax/categorical sampling. A single dispatch and a
    single host read for the whole sequence — the TPU-idiomatic decode loop
    (the host-loop :func:`generate` pays one device round-trip per token,
    which dominates when the link to the chip is remote).

    Greedy (``temperature=0``) matches :func:`generate` exactly; sampling
    uses ``jax.random.categorical`` (a different RNG than the host loop's
    numpy, so draws differ — distributions match). ``top_k`` keeps only the
    k most likely tokens and ``top_p`` keeps the smallest nucleus whose
    probability mass reaches p (both on-device filters over the temperature-
    scaled distribution; combine freely — top_k applies first). Returns
    [N, n_new_tokens].
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    _require_graph(net, "generate_on_device")
    ids, empty = _prep_prompt(net, prompt_ids, n_new_tokens)
    if empty is not None:
        return empty

    from deeplearning4j_tpu.nn import helpers as _helpers
    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

    inp = net.conf.inputs[0]
    out_name = net.conf.outputs[0]
    greedy = not (temperature and temperature > 0)
    vocab_n = getattr(net.conf.vertices[out_name].obj, "n_out", 0)
    if greedy or top_k < 0 or (vocab_n and top_k >= vocab_n):
        top_k = 0  # no-op filter: don't let it fragment the compile cache
    if greedy or not (top_p and 0.0 < top_p < 1.0):
        top_p = 0.0
    key = ("generate", n_new_tokens, greedy, float(temperature),
           int(top_k), float(top_p), _helpers.version())
    if key not in net._jit_cache:
        net._evict_stale(_helpers.version())
        dtype = net.conf.global_conf.jnp_dtype()

        use_k = bool(top_k and top_k > 0)
        use_p = bool(top_p and 0.0 < top_p < 1.0)

        def sample(p, k):
            if greedy:
                return jnp.argmax(p, axis=-1).astype(jnp.int32)
            logits = jnp.log(jnp.maximum(p, 1e-20)) / temperature
            if use_k or use_p:
                srt = jnp.sort(logits, axis=-1)[:, ::-1]  # ONE descending sort
                if use_k:
                    kk = min(int(top_k), p.shape[-1])
                    logits = jnp.where(logits >= srt[..., kk - 1][..., None],
                                       logits, -jnp.inf)
                    # the nucleus then applies over the top-k survivors
                    srt = jnp.where(jnp.arange(srt.shape[-1]) < kk, srt,
                                    -jnp.inf)
                if use_p:
                    # keep the smallest prefix reaching mass top_p (>= 1 tok)
                    probs = jax.nn.softmax(srt, axis=-1)
                    csum = jnp.cumsum(probs, axis=-1)
                    keep = csum - probs < top_p
                    cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                                     keepdims=True)
                    logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
            return jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)

        def fn(params, states, prompt, rng_key):
            batch = prompt.shape[0]
            carries = {vd.name: vd.obj.init_carry(batch, dtype)
                       for vd in net.conf.layer_vertices()
                       if isinstance(vd.obj, BaseRecurrentLayer)}
            acts, _, _, carries = net._forward_all(
                params, states, {inp: prompt}, train=False, rng=None,
                carries=carries)
            keys = jax.random.split(rng_key, n_new_tokens)
            tok0 = sample(acts[out_name][:, -1], keys[0])

            def step(carry, k):
                carries, tok = carry
                x = tok[:, None, None].astype(dtype)
                acts, _, _, carries = net._forward_all(
                    params, states, {inp: x}, train=False, rng=None,
                    carries=carries)
                nxt = sample(acts[out_name][:, -1], k)
                return (carries, nxt), nxt

            _, toks = jax.lax.scan(step, (carries, tok0), keys[1:])
            return jnp.concatenate([tok0[:, None], toks.T], axis=1)

        net._jit_cache[key] = jax.jit(fn)
    toks = net._jit_cache[key](net.params, net.states,
                               jnp.asarray(ids, jnp.float32),
                               jax.random.PRNGKey(seed))
    return np.asarray(toks).astype(np.int64)


def beam_search(net, prompt_ids, n_new_tokens: int, beam_size: int = 4,
                eos_id: int = None, length_penalty: float = 0.0):
    """Device-side beam search over a :class:`TransformerLM`-style network:
    the beams ride the batch axis (N*beam KV caches), each `lax.scan` step
    scores beam*vocab continuations, takes the top-k, and RE-INDEXES every
    per-beam carry (KV caches included) with one gather — the whole search
    is a single compiled dispatch, like :func:`generate_on_device`.

    With ``eos_id``, finished beams only extend with ``eos_id`` at zero
    cost (score frozen). Raw scores are unnormalized log-prob sums, which
    favor beams that hit EOS early (shorter sums are less negative);
    ``length_penalty`` > 0 corrects that early-termination bias by ranking
    beams on ``score / length**length_penalty`` (GNMT-style; 1.0 = mean
    log-prob per token, 0.0 = raw sums, the biased legacy behavior).
    Returns ``(tokens [N, n_new_tokens], scores [N])`` for the best beam
    per batch row; scores are the ranking values (normalized when
    ``length_penalty`` > 0).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    _require_graph(net, "beam_search")
    if length_penalty < 0:
        raise ValueError(
            f"length_penalty must be >= 0 (got {length_penalty}); 0 disables "
            "normalization, larger values favor longer beams")
    ids, empty = _prep_prompt(net, prompt_ids, n_new_tokens)
    if empty is not None:
        return empty, np.zeros((ids.shape[0],), np.float32)
    n_batch, b = ids.shape[0], int(beam_size)

    from deeplearning4j_tpu.nn import helpers as _helpers
    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

    inp = net.conf.inputs[0]
    out_name = net.conf.outputs[0]
    key = ("beam", n_new_tokens, b, eos_id, float(length_penalty),
           _helpers.version())
    if key not in net._jit_cache:
        net._evict_stale(_helpers.version())
        dtype = net.conf.global_conf.jnp_dtype()

        def gather_beams(carries, flat_idx, nb):
            # reindex batch-leading carry leaves; scalars (positions) pass
            return jax.tree_util.tree_map(
                lambda a: a[flat_idx] if (hasattr(a, "ndim") and a.ndim >= 1
                                          and a.shape[0] == nb) else a,
                carries)

        def select(scores, finished, logp, n, v):
            """Top-b continuations over beam*vocab."""
            if eos_id is not None:
                cont = jnp.full((v,), -1e30).at[eos_id].set(0.0)
                logp = jnp.where(finished[..., None], cont, logp)
            total = scores[..., None] + logp            # [N, B, V]
            new_scores, flat = jax.lax.top_k(total.reshape(n, b * v), b)
            beam_idx = flat // v                         # [N, B]
            tok = (flat % v).astype(jnp.int32)
            return new_scores, beam_idx, tok

        def fn(params, states, prompt):
            n, t0 = prompt.shape
            nb = n * b
            # prefill ONCE per batch row; beams split only after the prompt
            carries = {vd.name: vd.obj.init_carry(n, dtype)
                       for vd in net.conf.layer_vertices()
                       if isinstance(vd.obj, BaseRecurrentLayer)}
            acts, _, _, carries = net._forward_all(
                params, states, {inp: prompt}, train=False, rng=None,
                carries=carries)
            logp = jnp.log(jnp.maximum(acts[out_name][:, -1], 1e-20))
            v = logp.shape[-1]
            # replicate the prompt's caches across the beam axis
            carries = jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, b, axis=0)
                if (hasattr(a, "ndim") and a.ndim >= 1 and a.shape[0] == n)
                else a, carries)
            # first selection: top-b distinct tokens straight from the
            # prompt distribution (all beams would be identical anyway)
            scores, tok = jax.lax.top_k(logp.astype(jnp.float32), b)
            tok = tok.astype(jnp.int32)                  # [N, B]
            finished = (tok == eos_id) if eos_id is not None \
                else jnp.zeros((n, b), bool)
            row = jnp.arange(n)[:, None] * b
            toks = jnp.zeros((n, b, n_new_tokens), jnp.int32)
            toks = toks.at[:, :, 0].set(tok)
            use_len = bool(length_penalty > 0)
            # tokens before/incl. EOS; scalar placeholder keeps the carry
            # structure stable when normalization is off (no dead gathers)
            length = (jnp.ones((n, b), jnp.float32) if use_len
                      else jnp.zeros(()))

            def step(carry, i):
                carries, toks, scores, finished, length, last = carry
                x = last.reshape(nb)[:, None, None].astype(dtype)
                acts, _, _, carries = net._forward_all(
                    params, states, {inp: x}, train=False, rng=None,
                    carries=carries)
                logp = jnp.log(jnp.maximum(acts[out_name][:, -1], 1e-20))
                logp = logp.reshape(n, b, v).astype(jnp.float32)
                scores, beam_idx, tok = select(scores, finished, logp, n, v)
                flat_idx = (row + beam_idx).reshape(-1)
                carries = gather_beams(carries, flat_idx, nb)
                toks = jnp.take_along_axis(toks, beam_idx[:, :, None], axis=1)
                finished = jnp.take_along_axis(finished, beam_idx, axis=1)
                if use_len:
                    length = jnp.take_along_axis(length, beam_idx, axis=1)
                    length = jnp.where(finished, length, length + 1.0)
                toks = jax.lax.dynamic_update_index_in_dim(
                    toks, tok, i, axis=2)
                if eos_id is not None:
                    finished = finished | (tok == eos_id)
                return (carries, toks, scores, finished, length, tok), None

            (carries, toks, scores, finished, length, _), _ = jax.lax.scan(
                step, (carries, toks, scores, finished, length, tok),
                jnp.arange(1, n_new_tokens))
            if use_len:
                scores = scores / jnp.maximum(length, 1.0) ** length_penalty
            best = jnp.argmax(scores, axis=1)
            return (jnp.take_along_axis(
                        toks, best[:, None, None], axis=1)[:, 0],
                    jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0])

        net._jit_cache[key] = jax.jit(fn)
    toks, scores = net._jit_cache[key](net.params, net.states,
                                       jnp.asarray(ids, jnp.float32))
    return np.asarray(toks).astype(np.int64), np.asarray(scores)


def _require_graph(net, fn_name: str) -> None:
    """The compiled decode paths drive ComputationGraph internals
    (conf.vertices / conf.layer_vertices / conf.inputs); fail with a clear
    message instead of an AttributeError deep inside for other net types
    (the host-loop :func:`generate` handles MultiLayerNetwork)."""
    conf = getattr(net, "conf", None)
    if not (hasattr(conf, "vertices") and hasattr(conf, "inputs")):
        raise TypeError(
            f"{fn_name} requires a ComputationGraph-based network "
            f"(e.g. TransformerLM.build()); got {type(net).__name__}. "
            "Use generate() for MultiLayerNetwork models.")


def _prep_prompt(net, prompt_ids, n_new_tokens: int):
    """Shared generate prologue: normalize the prompt to [N,T], early-out
    for n_new_tokens<=0, and reject sequences the decode caches cannot hold.
    Returns (ids, empty_result_or_None)."""
    import numpy as np

    ids = np.asarray(prompt_ids)
    if ids.ndim == 1:
        ids = ids[None]
    if n_new_tokens <= 0:
        return ids, np.zeros((ids.shape[0], 0), np.int64)
    cap = _kv_capacity(net)
    total = ids.shape[1] + n_new_tokens - 1  # last token is never fed back
    if cap is not None and total > cap:
        raise ValueError(
            f"prompt ({ids.shape[1]}) + {n_new_tokens} new tokens needs "
            f"{total} cache slots but the model holds {cap} "
            f"(max_length/max_cache)")
    return ids, None


def _kv_capacity(net):
    """Smallest stateful-decode capacity across the net's layers (KV caches
    and positional tables), or None if the net has none."""
    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

    layer_vertices = getattr(net.conf, "layer_vertices", None)
    layers = ([vd.obj for vd in layer_vertices()] if layer_vertices
              else getattr(net, "layers", []))
    caps = [obj.carry_capacity() for obj in layers
            if isinstance(obj, BaseRecurrentLayer)
            and obj.carry_capacity() is not None]
    return min(caps) if caps else None


def transformer_encoder_block(g, name: str, src: str, d_model: int,
                              n_heads: int, d_ff: int,
                              attn_dropout: float = 0.0) -> str:
    """One pre-activation-free (post-LN, BERT-style) encoder block as graph
    vertices: self-attention + residual + LayerNorm, position-wise FFN +
    residual + LayerNorm. Returns the output vertex name."""
    from deeplearning4j_tpu.nn.layers import (
        LayerNormalizationLayer,
        SelfAttentionLayer,
    )
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex

    g.add_layer(f"{name}-att",
                SelfAttentionLayer(n_heads=n_heads,
                                   head_size=d_model // n_heads,
                                   project_input=True,
                                   attn_dropout=attn_dropout), src)
    g.add_vertex(f"{name}-res1", ElementWiseVertex(op="add"),
                 src, f"{name}-att")
    g.add_layer(f"{name}-ln1", LayerNormalizationLayer(), f"{name}-res1")
    g.add_layer(f"{name}-ff1", DenseLayer(n_in=d_model, n_out=d_ff,
                                          activation="gelu"), f"{name}-ln1")
    g.add_layer(f"{name}-ff2", DenseLayer(n_in=d_ff, n_out=d_model,
                                          activation="identity"),
                f"{name}-ff1")
    g.add_vertex(f"{name}-res2", ElementWiseVertex(op="add"),
                 f"{name}-ln1", f"{name}-ff2")
    g.add_layer(f"{name}-ln2", LayerNormalizationLayer(), f"{name}-res2")
    return f"{name}-ln2"


@register_zoo_model
class TransformerEncoder(ZooModel):
    """BERT-base-shape transformer encoder for sequence classification
    (no reference counterpart — the snapshot predates attention; this is the
    framework-native builder behind the BASELINE "BERT-base" config, whose
    import path lives in ``modelimport/keras``).

    Defaults are BERT-base: 12 layers, d_model 768, 12 heads, d_ff 3072.
    Token ids [N,T] → embeddings + learned positions → N encoder blocks →
    mean-pool → classifier.
    """

    def __init__(self, num_labels: int = 2, seed: int = 123,
                 vocab_size: int = 30522, max_length: int = 128,
                 n_layers: int = 12, d_model: int = 768, n_heads: int = 12,
                 d_ff: int = 3072):
        super().__init__(num_labels, seed)
        self.vocab_size = vocab_size
        self.max_length = max_length
        self.n_layers = n_layers
        self.d_model = d_model
        self.n_heads = n_heads
        self.d_ff = d_ff

    def meta_data(self):
        return ModelMetaData(((self.max_length,),), 1, "rnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer,
            GlobalPoolingLayer,
            PositionalEmbeddingLayer,
        )

        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(1e-4)).graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(1, self.max_length)))
        g.add_layer("embed",
                    EmbeddingSequenceLayer(n_in=self.vocab_size,
                                           n_out=self.d_model), "tokens")
        g.add_layer("pos", PositionalEmbeddingLayer(n_in=self.d_model,
                                                    max_len=self.max_length),
                    "embed")
        src = "pos"
        for i in range(self.n_layers):
            src = transformer_encoder_block(g, f"block{i}", src,
                                            self.d_model, self.n_heads,
                                            self.d_ff)
        g.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), src)
        g.add_layer("out", OutputLayer(n_in=self.d_model,
                                       n_out=self.num_labels,
                                       activation="softmax", loss="mcxent"),
                    "pool")
        g.set_outputs("out")
        return g.build()


@register_zoo_model
class VisionTransformer(ZooModel):
    """ViT (Dosovitskiy et al. 2020) — patchify-and-attend image
    classifier (no reference counterpart; the conv+attention composition
    the snapshot-era zoo could not express, built entirely from this
    framework's vertices).

    Images [N,H,W,C] → non-overlapping patch embedding (Conv2D with
    kernel == stride == patch) → [N, T=HW/p², d_model] token sequence →
    learned positions → encoder blocks (the TransformerEncoder blocks)
    → mean-pool → classifier. Defaults are ViT-Tiny-ish for trainability
    at test scale; pass ViT-B/16 numbers (12 layers, d_model 768,
    12 heads, d_ff 3072, patch 16, image 224) for the paper shape.
    """

    def __init__(self, num_labels: int = 10, seed: int = 123,
                 image_size: int = 32, channels: int = 3,
                 patch_size: int = 4, n_layers: int = 4,
                 d_model: int = 64, n_heads: int = 4, d_ff: int = 128):
        super().__init__(num_labels, seed)
        if image_size % patch_size != 0:
            raise ValueError(
                f"image_size {image_size} not divisible by patch_size "
                f"{patch_size}")
        self.image_size = image_size
        self.channels = channels
        self.patch_size = patch_size
        self.n_layers = n_layers
        self.d_model = d_model
        self.n_heads = n_heads
        self.d_ff = d_ff

    @property
    def num_patches(self) -> int:
        side = self.image_size // self.patch_size
        return side * side

    def meta_data(self):
        return ModelMetaData(
            ((self.channels, self.image_size, self.image_size),), 1, "cnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            GlobalPoolingLayer,
            PositionalEmbeddingLayer,
        )
        from deeplearning4j_tpu.nn.vertices import ReshapeVertex

        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(3e-4)).graph_builder()
             .add_inputs("image")
             .set_input_types(InputType.convolutional(
                 self.image_size, self.image_size, self.channels)))
        # one conv with kernel == stride IS the patch embedding: each
        # patch hits the MXU as a single [p*p*C, d_model] matmul
        g.add_layer("patch",
                    ConvolutionLayer(n_out=self.d_model,
                                     kernel_size=(self.patch_size,
                                                  self.patch_size),
                                     stride=(self.patch_size,
                                             self.patch_size),
                                     activation="identity"), "image")
        g.add_vertex("tokens",
                     ReshapeVertex(shape=(self.num_patches, self.d_model)),
                     "patch")
        g.add_layer("pos",
                    PositionalEmbeddingLayer(n_in=self.d_model,
                                             max_len=self.num_patches),
                    "tokens")
        src = "pos"
        for i in range(self.n_layers):
            src = transformer_encoder_block(g, f"block{i}", src,
                                            self.d_model, self.n_heads,
                                            self.d_ff)
        g.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), src)
        g.add_layer("out", OutputLayer(n_in=self.d_model,
                                       n_out=self.num_labels,
                                       activation="softmax", loss="mcxent"),
                    "pool")
        g.set_outputs("out")
        return g.build()


def _gated_dense_ff(g, prefix: str, src: str, d_model: int,
                    d_ff: int) -> str:
    """``W2(silu(W1 x) * W3 x)`` without biases as three dense vertices
    ``<prefix>1``, ``<prefix>3``, ``<prefix>2`` and their product
    ``<prefix>g``. Returns the output vertex name."""
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex

    for part, activation in (("1", "silu"), ("3", "identity")):
        g.add_layer(prefix + part,
                    DenseLayer(n_in=d_model, n_out=d_ff, has_bias=False,
                               activation=activation), src)
    g.add_vertex(prefix + "g", ElementWiseVertex(op="product"),
                 prefix + "1", prefix + "3")
    g.add_layer(prefix + "2", DenseLayer(n_in=d_ff, n_out=d_model,
                                         has_bias=False,
                                         activation="identity"), prefix + "g")
    return prefix + "2"


def hybrid_conv_moe_block(g, name: str, src: str, operator: str, *,
                          d_model: int, n_heads: int, n_kv_heads: int,
                          rope_theta: float, conv_kernel: int, norm_eps: float,
                          max_len: int, dense_ff: int = 0,
                          experts: dict = None) -> str:
    """One pre-norm block of the LFM2 family: RMSNorm → operator → residual,
    RMSNorm → feed-forward → residual. ``operator`` is ``conv`` (a gated
    short convolution) or ``full_attention`` (grouped-query rotary attention
    with QK-norm, no biases); the feed-forward is a gated dense one of width
    ``dense_ff``, or a sparse expert layer built from ``experts`` (the
    keyword arguments of :class:`MixtureOfExpertsLayer`). Returns the output
    vertex name."""
    from deeplearning4j_tpu.nn.layers import (
        GatedShortConvLayer,
        GroupedQueryAttentionLayer,
        MixtureOfExpertsLayer,
        RMSNormLayer,
    )
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex

    g.add_layer(f"{name}-norm1", RMSNormLayer(eps=norm_eps), src)
    if operator == "conv":
        op = f"{name}-conv"
        g.add_layer(op, GatedShortConvLayer(n_out=d_model,
                                            kernel_size=conv_kernel,
                                            activation="identity"),
                    f"{name}-norm1")
    elif operator == "full_attention":
        op = f"{name}-att"
        g.add_layer(op, GroupedQueryAttentionLayer(
            n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_size=d_model // n_heads, use_bias=False, qk_norm=True,
            qk_norm_eps=norm_eps, rope_theta=rope_theta, max_cache=max_len,
            activation="identity"), f"{name}-norm1")
    else:
        raise ValueError(f"layer type {operator!r} is neither 'conv' nor "
                         f"'full_attention'")
    g.add_vertex(f"{name}-res1", ElementWiseVertex(op="add"), src, op)
    g.add_layer(f"{name}-norm2", RMSNormLayer(eps=norm_eps), f"{name}-res1")
    if experts is None:
        ff = _gated_dense_ff(g, f"{name}-ff", f"{name}-norm2", d_model,
                             dense_ff)
    else:
        ff = f"{name}-moe"
        g.add_layer(ff, MixtureOfExpertsLayer(n_in=d_model, n_out=d_model,
                                              **experts), f"{name}-norm2")
    g.add_vertex(f"{name}-res2", ElementWiseVertex(op="add"),
                 f"{name}-res1", ff)
    return f"{name}-res2"


@register_zoo_model
class HybridConvMoELM(ZooModel):
    """Causal language model of the LFM2-MoE family (LiquidAI LFM2-8B-A1B):
    token ids [N,T] → embedding → blocks whose operator differs by layer
    (``layer_types[l]``: ``conv`` or ``full_attention``) and whose
    feed-forward is dense for the first ``num_dense_layers`` blocks and a
    sparse expert layer after them (sigmoid routing with a selection-only
    expert bias, normalised top-k weights, SwiGLU experts) → RMSNorm →
    untied softmax head. No positional-embedding vertex: the attention
    layers rotate q and k. Labels as for :class:`TransformerLM`: int32
    class ids [N,T] (:func:`lm_labels`).

    ``experts_held=(first, count)`` builds every expert layer as that share
    of the experts (see :class:`MixtureOfExpertsLayer`): what one chip of an
    expert-parallel group holds. Defaults are the published sizes.
    """

    def __init__(self, num_labels: int = 0, seed: int = 123,
                 vocab_size: int = 65536, max_length: int = 8192,
                 layer_types=("conv", "conv", "full_attention", "conv",
                              "conv", "conv", "full_attention", "conv",
                              "conv", "conv", "full_attention", "conv",
                              "conv", "conv", "full_attention", "conv",
                              "conv", "conv", "full_attention", "conv",
                              "conv", "full_attention", "conv", "conv"),
                 num_dense_layers: int = 2, d_model: int = 2048,
                 n_heads: int = 32, n_kv_heads: int = 8, d_ff: int = 7168,
                 n_experts: int = 32, experts_per_token: int = 4,
                 expert_d_ff: int = 1792, experts_held=None,
                 use_expert_bias: bool = True, norm_topk: bool = True,
                 routed_scaling: float = 1.0, conv_kernel: int = 3,
                 rope_theta: float = 1e6, norm_eps: float = 1e-5):
        vocab_size = num_labels or vocab_size
        super().__init__(vocab_size, seed)
        self.vocab_size = vocab_size
        self.max_length = max_length
        self.layer_types = tuple(layer_types)
        self.num_dense_layers = num_dense_layers
        self.block = dict(d_model=d_model, n_heads=n_heads,
                          n_kv_heads=n_kv_heads, rope_theta=rope_theta,
                          conv_kernel=conv_kernel, norm_eps=norm_eps,
                          max_len=max_length)
        self.d_ff = d_ff
        self.experts = dict(
            n_experts=n_experts, top_k=experts_per_token, n_hidden=expert_d_ff,
            gated=True, activation="silu", gate="sigmoid",
            expert_bias=use_expert_bias, norm_topk=norm_topk,
            routed_scaling=routed_scaling, experts_held=experts_held)

    def meta_data(self):
        return ModelMetaData(((self.max_length,),), 1, "rnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer,
            RMSNormLayer,
        )

        d_model = self.block["d_model"]
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(3e-4)).graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(1, self.max_length)))
        g.add_layer("embed", EmbeddingSequenceLayer(n_in=self.vocab_size,
                                                    n_out=d_model), "tokens")
        src = "embed"
        for i, operator in enumerate(self.layer_types):
            dense = i < self.num_dense_layers
            src = hybrid_conv_moe_block(
                g, f"block{i}", src, operator, dense_ff=self.d_ff,
                experts=None if dense else self.experts, **self.block)
        g.add_layer("norm_f", RMSNormLayer(eps=self.block["norm_eps"]), src)
        g.add_layer("out", RnnOutputLayer(n_in=d_model, n_out=self.vocab_size,
                                          has_bias=False,
                                          activation="softmax", loss="mcxent"),
                    "norm_f")
        g.set_outputs("out")
        return g.build()


def gated_window_moe_block(g, name: str, src: str, layer_type: str, *,
                           d_model: int, n_heads: int, n_kv_heads: int,
                           head_size: int, window: int, rope_theta: float,
                           norm_eps: float, max_len: int, dense_ff: int = 0,
                           experts: dict = None, shared_ff: int = 0) -> str:
    """One block of the AFMoE family (arcee-ai Trinity): an RMSNorm before
    and after each half, ``h + norm(attention(norm(h)))`` then ``h +
    norm(ff(norm(h)))``. The attention is grouped-query with QK-norm, no
    biases and a sigmoid gate on its output; a ``sliding_attention`` layer
    (vertex ``<name>-swa``) sees its last ``window`` keys and rotates q and
    k, a ``full_attention`` layer (``<name>-att``) sees every earlier key
    and no positions. The feed-forward is a gated dense one of width
    ``dense_ff`` or, from ``experts`` (the keyword arguments of
    :class:`MixtureOfExpertsLayer`), a sparse expert layer ``<name>-moe``
    plus, with ``shared_ff``, a gated dense one of that width that every
    token passes through (``<name>-shared1|3|2``). Returns the output
    vertex name."""
    from deeplearning4j_tpu.nn.layers import (
        GroupedQueryAttentionLayer,
        MixtureOfExpertsLayer,
        RMSNormLayer,
    )
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex

    if layer_type not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer type {layer_type!r} is neither "
                         f"'sliding_attention' nor 'full_attention'")
    sliding = layer_type == "sliding_attention"
    op = f"{name}-swa" if sliding else f"{name}-att"
    g.add_layer(f"{name}-norm1", RMSNormLayer(eps=norm_eps), src)
    g.add_layer(op, GroupedQueryAttentionLayer(
        n_heads=n_heads, n_kv_heads=n_kv_heads, head_size=head_size,
        use_bias=False, qk_norm=True, qk_norm_eps=norm_eps,
        rope_theta=rope_theta if sliding else None,
        window=window if sliding else None, output_gate=True,
        max_cache=max_len, activation="identity"), f"{name}-norm1")
    g.add_layer(f"{name}-norm1p", RMSNormLayer(eps=norm_eps), op)
    g.add_vertex(f"{name}-res1", ElementWiseVertex(op="add"), src,
                 f"{name}-norm1p")
    g.add_layer(f"{name}-norm2", RMSNormLayer(eps=norm_eps), f"{name}-res1")
    if experts is None:
        ff = _gated_dense_ff(g, f"{name}-ff", f"{name}-norm2", d_model,
                             dense_ff)
    else:
        ff = f"{name}-moe"
        g.add_layer(ff, MixtureOfExpertsLayer(n_in=d_model, n_out=d_model,
                                              **experts), f"{name}-norm2")
        if shared_ff:
            shared = _gated_dense_ff(g, f"{name}-shared", f"{name}-norm2",
                                     d_model, shared_ff)
            ff = f"{name}-ffsum"
            g.add_vertex(ff, ElementWiseVertex(op="add"), f"{name}-moe",
                         shared)
    g.add_layer(f"{name}-norm2p", RMSNormLayer(eps=norm_eps), ff)
    g.add_vertex(f"{name}-res2", ElementWiseVertex(op="add"),
                 f"{name}-res1", f"{name}-norm2p")
    return f"{name}-res2"


@register_zoo_model
class GatedWindowMoELM(ZooModel):
    """Causal language model of the AFMoE family (arcee-ai Trinity-Mini):
    token ids [N,T] → embedding times ``sqrt(d_model)`` → blocks
    (:func:`gated_window_moe_block`) whose attention is a sliding window or
    full by ``layer_types[l]`` and whose feed-forward is dense for the
    first ``num_dense_layers`` blocks and, after them, sparse experts
    (sigmoid routing with a selection-only bias, top-k weights normalised
    and scaled by ``route_scale``) beside a shared expert → RMSNorm →
    untied softmax head. Labels as for :class:`TransformerLM`: int32 class
    ids [N,T] (:func:`lm_labels`).

    ``experts_held=(first, count)`` builds every expert layer as that share
    of the experts (see :class:`MixtureOfExpertsLayer`); the shared expert
    is whole in every share. Defaults are the published sizes.
    ``learning_rate`` is Adam's: a float or a
    :class:`~deeplearning4j_tpu.nn.updaters.Schedule` (a warm-up, say).
    """

    def __init__(self, num_labels: int = 0, seed: int = 123,
                 vocab_size: int = 200192, max_length: int = 8192,
                 layer_types=("sliding_attention", "sliding_attention",
                              "sliding_attention", "full_attention") * 8,
                 num_dense_layers: int = 2, d_model: int = 2048,
                 n_heads: int = 32, n_kv_heads: int = 4, head_size: int = 128,
                 d_ff: int = 6144, n_experts: int = 128,
                 experts_per_token: int = 8, expert_d_ff: int = 1024,
                 n_shared_experts: int = 1, experts_held=None,
                 sliding_window: int = 2048, rope_theta: float = 1e4,
                 route_norm: bool = True, route_scale: float = 2.826,
                 scale_embedding: bool = True, norm_eps: float = 1e-5,
                 learning_rate=3e-4):
        vocab_size = num_labels or vocab_size
        super().__init__(vocab_size, seed)
        self.vocab_size = vocab_size
        self.max_length = max_length
        self.layer_types = tuple(layer_types)
        self.num_dense_layers = num_dense_layers
        self.scale_embedding = scale_embedding
        self.learning_rate = learning_rate
        self.block = dict(d_model=d_model, n_heads=n_heads,
                          n_kv_heads=n_kv_heads, head_size=head_size,
                          window=sliding_window, rope_theta=rope_theta,
                          norm_eps=norm_eps, max_len=max_length)
        self.d_ff = d_ff
        self.shared_ff = n_shared_experts * expert_d_ff
        self.experts = dict(
            n_experts=n_experts, top_k=experts_per_token, n_hidden=expert_d_ff,
            gated=True, activation="silu", gate="sigmoid", expert_bias=True,
            norm_topk=route_norm, norm_topk_eps=1e-20,
            routed_scaling=route_scale, experts_held=experts_held)

    def meta_data(self):
        return ModelMetaData(((self.max_length,),), 1, "rnn")

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer,
            RMSNormLayer,
        )
        from deeplearning4j_tpu.nn.vertices import ScaleVertex

        d_model = self.block["d_model"]
        g = (NeuralNetConfiguration.builder().seed(self.seed)
             .weight_init("xavier").updater(Adam(self.learning_rate))
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(1, self.max_length)))
        g.add_layer("embed", EmbeddingSequenceLayer(n_in=self.vocab_size,
                                                    n_out=d_model), "tokens")
        src = "embed"
        if self.scale_embedding:
            src = "embed-scale"
            g.add_vertex(src, ScaleVertex(scale_factor=d_model ** 0.5),
                         "embed")
        for i, layer_type in enumerate(self.layer_types):
            dense = i < self.num_dense_layers
            src = gated_window_moe_block(
                g, f"block{i}", src, layer_type, dense_ff=self.d_ff,
                experts=None if dense else self.experts,
                shared_ff=0 if dense else self.shared_ff, **self.block)
        g.add_layer("norm_f", RMSNormLayer(eps=self.block["norm_eps"]), src)
        g.add_layer("out", RnnOutputLayer(n_in=d_model, n_out=self.vocab_size,
                                          has_bias=False,
                                          activation="softmax", loss="mcxent"),
                    "norm_f")
        g.set_outputs("out")
        return g.build()
