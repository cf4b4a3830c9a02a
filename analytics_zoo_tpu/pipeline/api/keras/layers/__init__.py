from .core import (
    Dense, SparseDense, Activation, Dropout, SpatialDropout1D,
    SpatialDropout2D, SpatialDropout3D, Flatten, Reshape, Permute,
    RepeatVector, Masking, Highway, MaxoutDense, TimeDistributed)
from .convolutional import (
    Convolution1D, Convolution2D, Convolution3D, AtrousConvolution1D,
    AtrousConvolution2D, ShareConvolution2D, SeparableConvolution2D,
    Deconvolution2D, LocallyConnected1D, LocallyConnected2D,
    ZeroPadding1D, ZeroPadding2D, ZeroPadding3D, Cropping1D, Cropping2D,
    Cropping3D, UpSampling1D, UpSampling2D, UpSampling3D, ResizeBilinear,
    SpaceToDepth2D)
from .pooling import (
    MaxPooling1D, MaxPooling2D, MaxPooling3D, AveragePooling1D,
    AveragePooling2D, AveragePooling3D, GlobalMaxPooling1D,
    GlobalMaxPooling2D, GlobalMaxPooling3D, GlobalAveragePooling1D,
    GlobalAveragePooling2D, GlobalAveragePooling3D)
from .normalization import (BatchNormalization, WithinChannelLRN2D, LRN2D,
                            LayerNorm, RMSNorm)
from .embedding import (Embedding, SparseEmbedding, TiedEmbedding,
                        WordEmbedding)
from .merge import Merge, merge
from .advanced_activations import (ELU, LeakyReLU, PReLU, SReLU,
                                   ThresholdedReLU)
from .noise import GaussianNoise, GaussianDropout
from .recurrent import SimpleRNN, LSTM, GRU, ConvLSTM2D, Bidirectional
from .torch_style import (
    AddConstant, MulConstant, BinaryThreshold, Threshold, HardShrink,
    SoftShrink, HardTanh, RReLU, Exp, Log, Sqrt, Square, Negative, Identity,
    Power, Mul, CAdd, CMul, Scale, GaussianSampler, KerasLayerWrapper,
    Narrow, Select, Squeeze)
from .moe import SwitchMoE, TopKMoE
from .ssm import GatedMLP, Mamba2Mixer
from .attention import (GroupedQueryAttention, MultiHeadSelfAttention,
                        PositionalEmbedding)
from ..engine import Sequential, Model
from .....core.graph import Input, InputLayer
