"""paddle_tpu.nn — layers & functional ops (reference: python/paddle/nn)."""
from ..optimizer import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: F401
                         ClipGradByValue)
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import utils  # noqa: F401
from .layer import Layer, LayerList, Parameter, ParameterList, Sequential  # noqa: F401
from .layers import (GELU, SiLU, AdaptiveAvgPool2D, AvgPool2D,  # noqa: F401
                     BatchNorm1D, BatchNorm2D, BatchNorm3D, BCEWithLogitsLoss,
                     Conv2D, CrossEntropyLoss, Dropout, Embedding, Flatten,
                     GroupNorm, Hardsigmoid, Hardswish, L1Loss, LayerNorm,
                     LeakyReLU, Linear, LogSoftmax, MaxPool2D, Mish, MSELoss,
                     MultiHeadAttention, NLLLoss, ReLU, ReLU6, RMSNorm,
                     Sigmoid, SmoothL1Loss, Softmax, Softplus, Tanh,
                     TransformerEncoder, TransformerEncoderLayer)
from .layers import (AdaptiveMaxPool2D, AvgPool1D, Conv1D, Conv3D,  # noqa: F401
                     Conv2DTranspose, CosineEmbeddingLoss, CosineSimilarity,
                     CTCLoss, GLU, HingeEmbeddingLoss, Identity,
                     InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
                     KLDivLoss, MarginRankingLoss, MaxPool1D,
                     PairwiseDistance, PixelShuffle, PixelUnshuffle, PReLU,
                     SpectralNorm, Transformer, TransformerDecoder,
                     TransformerDecoderLayer, TripletMarginLoss, Unflatten,
                     Upsample, UpsamplingBilinear2D, UpsamplingNearest2D)
from .dropless_moe import DroplessMoE, SwiGLU  # noqa: F401
from .rnn import (GRU, LSTM, RNN, BiRNN, GRUCell, LSTMCell,  # noqa: F401
                  SimpleRNN, SimpleRNNCell)
from .layers_ext import *  # noqa: F401,F403,E402  (long-tail layer classes)
from .layers_ext import dynamic_decode  # noqa: F401
