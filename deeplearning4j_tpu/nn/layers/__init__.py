"""Layer catalog — config-first, JSON-round-trippable.

Parity with DL4J's layer conf + impl split
(deeplearning4j-nn ``org/deeplearning4j/nn/conf/layers/`` configs and
``org/deeplearning4j/nn/layers/`` implementations).  Here each layer is ONE
dataclass carrying its hyperparameters (the conf) plus pure functions
``init_params``/``apply`` (the impl) — forward is a pure jax function,
backward comes from autodiff, and XLA is the "cuDNN helper".

The JSON-subtype registry mirrors DL4J's Jackson ``@JsonSubTypes``
custom-layer SPI: ``register_layer`` makes any layer (including user-defined
ones) serializable by type name.
"""

from deeplearning4j_tpu.nn.layers.base import (
    Layer,
    register_layer,
    layer_from_dict,
    layer_registry,
)
from deeplearning4j_tpu.nn.layers.core import (
    DenseLayer,
    OutputLayer,
    LossLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
    EmbeddingSequenceLayer,
    BatchNormalization,
)
from deeplearning4j_tpu.nn.layers.conv import (
    ConvolutionLayer,
    Convolution1DLayer,
    Convolution3DLayer,
    SeparableConvolution2D,
    DepthwiseConvolution2D,
    Deconvolution2D,
    SubsamplingLayer,
    Subsampling1DLayer,
    Subsampling3DLayer,
    UpsamplingLayer,
    ZeroPaddingLayer,
    CroppingLayer,
    SpaceToDepthLayer,
    GlobalPoolingLayer,
    LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTM,
    GravesLSTM,
    SimpleRnn,
    GRU,
    Bidirectional,
    BidirectionalLastStep,
    LastTimeStep,
    TimeDistributed,
    RnnOutputLayer,
    RnnLossLayer,
)
from deeplearning4j_tpu.nn.layers.attention import (
    SelfAttentionLayer,
    LearnedSelfAttentionLayer,
)
from deeplearning4j_tpu.nn.layers.norm import LayerNormalization, PReLULayer
from deeplearning4j_tpu.nn.layers.decoder import (
    RMSNorm,
    GatedFeedForward,
    LatentAttention,
    DeltaAttention,
    GatedShortConv,
    GroupedQueryAttention,
    RoutedExperts,
    CausalLMOutput,
)
from deeplearning4j_tpu.nn.layers.extra import (
    ZeroPadding1DLayer,
    Cropping1DLayer,
    Upsampling1DLayer,
    ZeroPadding3DLayer,
    Cropping3DLayer,
    Upsampling3DLayer,
    SpaceToBatchLayer,
    GaussianDropoutLayer,
    GaussianNoiseLayer,
    AlphaDropoutLayer,
    SpatialDropoutLayer,
    LocallyConnected1D,
    LocallyConnected2D,
    ElementWiseMultiplicationLayer,
    RepeatVector,
    MaskZeroLayer,
    GravesBidirectionalLSTM,
    CenterLossOutputLayer,
    Yolo2OutputLayer,
    VariationalAutoencoder,
    PrimaryCapsules,
    CapsuleLayer,
    CapsuleStrengthLayer,
    RecurrentAttentionLayer,
    MixtureOfExperts,
    PermuteLayer,
    SeparableConvolution1D,
    ConvLSTM2D,
)

__all__ = [
    "Layer", "register_layer", "layer_from_dict", "layer_registry",
    "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer", "DropoutLayer",
    "EmbeddingLayer", "EmbeddingSequenceLayer", "BatchNormalization",
    "ConvolutionLayer", "Convolution1DLayer", "Convolution3DLayer",
    "SeparableConvolution2D", "DepthwiseConvolution2D", "Deconvolution2D",
    "SubsamplingLayer", "Subsampling1DLayer", "Subsampling3DLayer",
    "UpsamplingLayer", "ZeroPaddingLayer", "CroppingLayer", "SpaceToDepthLayer",
    "GlobalPoolingLayer", "LocalResponseNormalization",
    "LSTM", "GravesLSTM", "SimpleRnn", "GRU", "Bidirectional",
    "BidirectionalLastStep", "LastTimeStep",
    "TimeDistributed", "RnnOutputLayer", "RnnLossLayer",
    "SelfAttentionLayer", "LearnedSelfAttentionLayer",
    "LayerNormalization", "PReLULayer",
    "RMSNorm", "GatedFeedForward", "LatentAttention", "DeltaAttention",
    "GatedShortConv", "GroupedQueryAttention", "RoutedExperts",
    "CausalLMOutput",
    "ZeroPadding1DLayer", "Cropping1DLayer", "Upsampling1DLayer",
    "ZeroPadding3DLayer", "Cropping3DLayer", "Upsampling3DLayer",
    "SpaceToBatchLayer", "GaussianDropoutLayer", "GaussianNoiseLayer",
    "AlphaDropoutLayer", "SpatialDropoutLayer", "LocallyConnected1D",
    "LocallyConnected2D", "ElementWiseMultiplicationLayer", "RepeatVector",
    "MaskZeroLayer", "GravesBidirectionalLSTM", "CenterLossOutputLayer",
    "Yolo2OutputLayer", "VariationalAutoencoder", "PrimaryCapsules",
    "CapsuleLayer", "CapsuleStrengthLayer", "RecurrentAttentionLayer",
    "MixtureOfExperts",
    "PermuteLayer", "SeparableConvolution1D", "ConvLSTM2D",
]
