"""Model zoo: 13 instantiable architectures + selector + pretrained loading.

Reference: ``deeplearning4j-zoo/`` (``ZooModel.java``, ``ModelSelector.java``,
13 models under ``zoo/model/``).
"""

from deeplearning4j_tpu.zoo.zoo_model import (
    ModelMetaData,
    ModelSelector,
    PretrainedType,
    ZooModel,
    register_zoo_model,
)
from deeplearning4j_tpu.zoo.models import (
    AlexNet,
    Darknet19,
    FaceNetNN4Small2,
    GatedWindowMoELM,
    GoogLeNet,
    HybridConvMoELM,
    InceptionResNetV1,
    LeNet,
    ResNet50,
    SimpleCNN,
    TextGenerationLSTM,
    TinyYOLO,
    TransformerEncoder,
    VisionTransformer,
    TransformerLM,
    VGG16,
    VGG19,
    YOLO2,
    beam_search,
    generate,
    generate_on_device,
    lm_labels,
)

__all__ = [
    "ModelMetaData", "ModelSelector", "PretrainedType", "ZooModel",
    "register_zoo_model",
    "AlexNet", "Darknet19", "FaceNetNN4Small2", "GatedWindowMoELM",
    "GoogLeNet", "HybridConvMoELM",
    "InceptionResNetV1", "LeNet", "ResNet50", "SimpleCNN",
    "TextGenerationLSTM", "TinyYOLO", "TransformerEncoder", "TransformerLM",
    "VisionTransformer",
    "VGG16", "VGG19", "YOLO2", "beam_search", "generate",
    "generate_on_device", "lm_labels",
]
from deeplearning4j_tpu.zoo.labels import (  # noqa: F401
    ClassPrediction,
    COCOLabels,
    ImageNetLabels,
    Labels,
    VOCLabels,
)
