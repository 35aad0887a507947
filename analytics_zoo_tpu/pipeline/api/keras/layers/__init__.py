"""The Keras-1-style layer library (reference: 116 layer files under
`Z/pipeline/api/keras/layers/` — SURVEY.md §2.4)."""

from analytics_zoo_tpu.pipeline.api.keras.layers.core import (
    Dense, Activation, Dropout, Flatten, Reshape, Permute, RepeatVector,
    Squeeze, ExpandDim, Narrow, Select, Masking)
from analytics_zoo_tpu.pipeline.api.keras.layers.conv import (
    Convolution1D, Convolution2D, Convolution3D, AtrousConvolution2D,
    SeparableConvolution2D, DepthwiseConvolution2D, Deconvolution2D,
    ZeroPadding1D, ZeroPadding2D,
    Cropping1D, Cropping2D, UpSampling1D, UpSampling2D, UpSampling3D,
    Conv1D, Conv2D, Conv3D, Conv2DTranspose, SeparableConv2D)
from analytics_zoo_tpu.pipeline.api.keras.layers.pooling import (
    MaxPooling1D, MaxPooling2D, MaxPooling3D,
    AveragePooling1D, AveragePooling2D, AveragePooling3D,
    GlobalMaxPooling1D, GlobalMaxPooling2D, GlobalMaxPooling3D,
    GlobalAveragePooling1D, GlobalAveragePooling2D, GlobalAveragePooling3D)
from analytics_zoo_tpu.pipeline.api.keras.layers.normalization import (
    BatchNormalization, LayerNormalization, WithinChannelLRN2D)
from analytics_zoo_tpu.pipeline.api.keras.layers.embedding import (
    Embedding, WordEmbedding)
from analytics_zoo_tpu.pipeline.api.keras.layers.recurrent import (
    SimpleRNN, LSTM, GRU, Bidirectional, TimeDistributed)
from analytics_zoo_tpu.pipeline.api.keras.layers.merge import (
    Merge, merge, Add, Multiply, Average, Maximum, Minimum, Concatenate,
    Dot)
from analytics_zoo_tpu.pipeline.api.keras.layers.advanced_activations \
    import (LeakyReLU, ELU, ThresholdedReLU, PReLU, SReLU, Softmax)
from analytics_zoo_tpu.pipeline.api.keras.layers.noise import (
    GaussianNoise, GaussianDropout, SpatialDropout1D, SpatialDropout2D,
    SpatialDropout3D)
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (
    MoE, GatedMLP, GroupLimitedMoE)
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder import (
    YarnRope, LatentAttention, GroupedQueryAttention, SparseIndexer,
    PatternDecoder, deepseek_v2_decoder, dots3_note_decoder,
    mimo_v2_flash_decoder)
from analytics_zoo_tpu.pipeline.api.keras.layers.transformer import (
    MultiHeadAttention, TransformerLayer, BERT)
from analytics_zoo_tpu.pipeline.api.keras.layers.elementwise import (
    AddConstant, MulConstant, CAdd, CMul, Mul, Scale, Power, Negative,
    Exp, Log, Sqrt, Square, Identity, BinaryThreshold, Threshold,
    HardShrink, SoftShrink, HardTanh, RReLU, GaussianSampler, GetShape,
    Expand, Max, ResizeBilinear, SelectTable, SplitTensor,
    KerasLayerWrapper, Highway, MaxoutDense)
from analytics_zoo_tpu.pipeline.api.keras.layers.local_conv import (
    LocallyConnected1D, LocallyConnected2D, AtrousConvolution1D,
    ShareConvolution2D, ZeroPadding3D, Cropping3D)
from analytics_zoo_tpu.pipeline.api.keras.layers.convlstm import (
    ConvLSTM2D, ConvLSTM3D)
from analytics_zoo_tpu.pipeline.api.keras.layers.sparse import (
    SparseEmbedding, SparseDense)

__all__ = [
    # core
    "Dense", "Activation", "Dropout", "Flatten", "Reshape", "Permute",
    "RepeatVector", "Squeeze", "ExpandDim", "Narrow", "Select", "Masking",
    # conv
    "Convolution1D", "Convolution2D", "Convolution3D",
    "AtrousConvolution2D", "SeparableConvolution2D",
    "DepthwiseConvolution2D", "Deconvolution2D",
    "ZeroPadding1D", "ZeroPadding2D", "Cropping1D", "Cropping2D",
    "UpSampling1D", "UpSampling2D", "UpSampling3D",
    "Conv1D", "Conv2D", "Conv3D", "Conv2DTranspose", "SeparableConv2D",
    # pooling
    "MaxPooling1D", "MaxPooling2D", "MaxPooling3D",
    "AveragePooling1D", "AveragePooling2D", "AveragePooling3D",
    "GlobalMaxPooling1D", "GlobalMaxPooling2D", "GlobalMaxPooling3D",
    "GlobalAveragePooling1D", "GlobalAveragePooling2D",
    "GlobalAveragePooling3D",
    # norm
    "BatchNormalization", "LayerNormalization", "WithinChannelLRN2D",
    # embedding
    "Embedding", "WordEmbedding",
    # recurrent
    "SimpleRNN", "LSTM", "GRU", "Bidirectional", "TimeDistributed",
    # merge
    "Merge", "merge", "Add", "Multiply", "Average", "Maximum", "Minimum",
    "Concatenate", "Dot",
    # advanced activations
    "LeakyReLU", "ELU", "ThresholdedReLU", "PReLU", "SReLU", "Softmax",
    # noise
    "GaussianNoise", "GaussianDropout", "SpatialDropout1D",
    "SpatialDropout2D", "SpatialDropout3D",
    # transformer
    "MultiHeadAttention", "TransformerLayer", "MoE", "BERT",
    "GatedMLP", "GroupLimitedMoE", "YarnRope", "LatentAttention",
    "SparseIndexer", "PatternDecoder", "deepseek_v2_decoder",
    "dots3_note_decoder", "mimo_v2_flash_decoder",
    "GroupedQueryAttention",
    # elementwise / tensor utilities
    "AddConstant", "MulConstant", "CAdd", "CMul", "Mul", "Scale", "Power",
    "Negative", "Exp", "Log", "Sqrt", "Square", "Identity",
    "BinaryThreshold", "Threshold", "HardShrink", "SoftShrink", "HardTanh",
    "RReLU", "GaussianSampler", "GetShape", "Expand", "Max",
    "ResizeBilinear", "SelectTable", "SplitTensor", "KerasLayerWrapper",
    "Highway", "MaxoutDense",
    # locally-connected / conv extras
    "LocallyConnected1D", "LocallyConnected2D", "AtrousConvolution1D",
    "ShareConvolution2D", "ZeroPadding3D", "Cropping3D",
    # conv-lstm
    "ConvLSTM2D", "ConvLSTM3D",
    # sparse
    "SparseEmbedding", "SparseDense",
]
