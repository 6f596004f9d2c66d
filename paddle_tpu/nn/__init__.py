"""``paddle_tpu.nn`` — neural network layers.

Reference parity: ``python/paddle/nn/`` (21.8 kLoC: Layer base +
layer/functional library) — see SURVEY.md §2.5 / A.6.
"""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import utils  # noqa: F401
from .layer.layers import Layer, ParamAttr  # noqa: F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
from .layer.activation import (  # noqa: F401
    ELU, GELU, GLU, Hardshrink, Hardsigmoid, Hardswish, Hardtanh, LeakyReLU,
    LogSigmoid, LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6, SELU, Sigmoid,
    Silu, Softmax, Softplus, Softshrink, Softsign, Swish, Tanh, Tanhshrink,
    ThresholdedReLU,
)
from .layer.common import (  # noqa: F401
    AlphaDropout, Bilinear, CosineSimilarity, Dropout, Dropout2D, Dropout3D,
    Embedding, Flatten, Identity, Linear, Pad1D, Pad2D, Pad3D,
    PairwiseDistance, PixelShuffle, Unfold, Upsample, UpsamplingBilinear2D,
    UpsamplingNearest2D,
)
from .layer.container import LayerDict, LayerList, ParameterList, Sequential  # noqa: F401
from .layer.conv import (  # noqa: F401
    Conv1D, Conv1DTranspose, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose,
)
from .layer.loss import (  # noqa: F401
    BCELoss, BCEWithLogitsLoss, CrossEntropyLoss, CTCLoss, HingeEmbeddingLoss,
    HSigmoidLoss, KLDivLoss, L1Loss, MSELoss, MarginRankingLoss, NLLLoss,
    SmoothL1Loss,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, GroupNorm, InstanceNorm1D,
    InstanceNorm2D, InstanceNorm3D, LayerNorm, LocalResponseNorm, RMSNorm,
    SpectralNorm,
    SyncBatchNorm,
)
from .layer.pooling import (  # noqa: F401
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D, AdaptiveMaxPool1D,
    AdaptiveMaxPool2D, AdaptiveMaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    MaxPool1D, MaxPool2D, MaxPool3D,
)
from .decode import BeamSearchDecoder, Decoder, dynamic_decode  # noqa: F401
from .layer.rnn import (  # noqa: F401
    GRU, LSTM, RNN, BiRNN, GRUCell, LSTMCell, RNNCellBase, SimpleRNN,
    SimpleRNNCell,
)
from .layer.moe import DepthMLPRouter, SparseExperts  # noqa: F401
from .layer.transformer import (  # noqa: F401
    GatedMLP, GroupedQueryAttention, MultiHeadAttention, Transformer, TransformerDecoder, TransformerDecoderLayer,
    TransformerEncoder, TransformerEncoderLayer,
)
from .layer.retention import PowerRetention, RetentionDecodeCache  # noqa: F401
from .layer.mamba import MambaDecodeCache, MambaMixer  # noqa: F401
from .layer.latent_attention import (  # noqa: F401
    LatentAttention, LatentDecodeCache, PagedLatentDecodeCache,
)
from .layer.cca_attention import CCADecodeCache, CCAttention  # noqa: F401
from .ssm import GatedSSMBlock, RecurrentDecodeCache, SSMLM  # noqa: F401
from . import lora  # noqa: F401
from .lora import attach_lora, load_adapter, unload_adapter  # noqa: F401
