"""``paddle_tpu.models`` — reference model zoo built purely on ``paddle_tpu.nn``.

Reference parity: the BASELINE.md workload ladder (LeNet → ResNet50 →
BERT-base → ERNIE → GPT-1.3B); the transformer stack mirrors what
``python/paddle/nn/layer/transformer.py`` (MultiHeadAttention:109,
TransformerEncoder:622) is used for in the reference's NLP model zoo.
Vision CNNs live in ``paddle_tpu.vision.models``.
"""
from .block_diffusion import (  # noqa: F401
    BlockDiffusionDecoderLayer,
    BlockDiffusionMoELM,
)
from .cca_moe import (  # noqa: F401
    CCAMoEDecoderLayer,
    CCAMoELM,
)
from .hybrid_mamba import (  # noqa: F401
    HybridMambaDecoderLayer,
    HybridMambaLM,
)
from .latent_moe import (  # noqa: F401
    LatentMoEDecoderLayer,
    LatentMoELM,
)
from .looped_lm import (  # noqa: F401
    LoopedDecoderLayer,
    LoopedLM,
)
from .power_retention import (  # noqa: F401
    PowerRetentionDecoderLayer,
    PowerRetentionLM,
)
from .window_moe import (  # noqa: F401
    WindowMoEDecoderLayer,
    WindowMoELM,
)
from .language_model import (  # noqa: F401
    TransformerForSequenceClassification,
    TransformerLM,
    TransformerLMCriterion,
    bert_base_config,
    ernie_base_config,
    gpt_1p3b_config,
)
