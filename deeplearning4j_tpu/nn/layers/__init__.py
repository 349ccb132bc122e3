"""Layer modules: each layer is a config dataclass carrying shape inference,
parameter initialization, and a pure functional forward pass.

Unlike DL4J's config/impl split (``nn/conf/layers/*`` vs ``nn/layers/*``)
there are no hand-written backprop pairs — ``jax.grad`` differentiates the
forward functions, and gradient-check tests (tests/test_gradients.py) keep the
math honest the same way DL4J's gradientcheck suites do.
"""

from deeplearning4j_tpu.nn.layers.base import Layer, LAYER_REGISTRY, layer_from_dict  # noqa: F401
from deeplearning4j_tpu.nn.layers.core import (  # noqa: F401
    DenseLayer,
    ActivationLayer,
    DropoutLayer,
    MaskLayer,
    EmbeddingLayer,
    EmbeddingSequenceLayer,
    PositionalEmbeddingLayer,
    ElementWiseMultiplicationLayer,
    PReLULayer,
)
from deeplearning4j_tpu.nn.layers.output import (  # noqa: F401
    OutputLayer,
    RnnOutputLayer,
    LossLayer,
    RnnLossLayer,
    CnnLossLayer,
    CenterLossOutputLayer,
)
from deeplearning4j_tpu.nn.layers.conv import (  # noqa: F401
    ConvolutionLayer,
    Convolution1DLayer,
    Deconvolution2DLayer,
    SeparableConvolution2DLayer,
    DepthwiseConvolution2DLayer,
    ZeroPaddingLayer,
    ZeroPadding1DLayer,
    CropLayer,
    SpaceToDepthLayer,
    SpaceToBatchLayer,
    UpsamplingLayer,
    Upsampling1DLayer,
)
from deeplearning4j_tpu.nn.layers.pooling import (  # noqa: F401
    SubsamplingLayer,
    Subsampling1DLayer,
    GlobalPoolingLayer,
)
from deeplearning4j_tpu.nn.layers.norm import (  # noqa: F401
    BatchNormalizationLayer,
    LayerNormalizationLayer,
    LocalResponseNormalizationLayer,
    RMSNormLayer,
)
from deeplearning4j_tpu.nn.layers.recurrent import (  # noqa: F401
    ConvLSTM2DLayer,
    GRULayer,
    LSTMLayer,
    GravesLSTMLayer,
    GravesBidirectionalLSTMLayer,
    SimpleRnnLayer,
    BidirectionalWrapper,
    LastTimeStepWrapper,
    MaskZeroLayer,
)
from deeplearning4j_tpu.nn.layers.autoencoder import AutoEncoderLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.vae import VariationalAutoencoderLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.vae_distributions import (  # noqa: F401
    BernoulliReconstructionDistribution,
    CompositeReconstructionDistribution,
    ExponentialReconstructionDistribution,
    GaussianReconstructionDistribution,
    LossFunctionWrapper,
    ReconstructionDistribution,
)
from deeplearning4j_tpu.nn.layers.objdetect import (  # noqa: F401
    DetectedObject,
    Yolo2OutputLayer,
    get_predicted_objects,
    nms,
)
from deeplearning4j_tpu.nn.layers.moe import MixtureOfExpertsLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.shortconv import GatedShortConvLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.wrappers import FrozenLayer, TimeDistributedWrapper  # noqa: F401
from deeplearning4j_tpu.nn.layers.samediff import SameDiffLayer, SameDiffLambdaLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.attention import (  # noqa: F401
    CausalSelfAttentionLayer,
    CrossAttentionLayer,
    GroupedQueryAttentionLayer,
    SelfAttentionLayer,
    LearnedSelfAttentionLayer,
)
