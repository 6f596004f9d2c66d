"""Decoder-only / encoder-only transformer language models — the flagship
benchmark workloads (BASELINE.md configs #3 BERT-base and #5 GPT-1.3B).

Built entirely from ``paddle_tpu.nn`` blocks (MultiHeadAttention /
TransformerEncoder — reference ``nn/layer/transformer.py:109,622``) with a
tied-embedding LM head and fused softmax-cross-entropy loss
(``operators/softmax_with_cross_entropy_op.cc:325`` semantics).

TPU-native notes: everything is static-shape and MXU-friendly (bf16-ready
matmuls, no data-dependent control flow); the causal mask is additive and
broadcast, so XLA fuses it into the attention softmax.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import tensor as T
from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..nn.layer.transformer import TransformerEncoder, TransformerEncoderLayer


def bert_base_config() -> dict:
    """BERT-base pretrain config (BASELINE.md workload #3)."""
    return dict(
        vocab_size=30528,  # 30522 padded to a multiple of 64 for the MXU
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position=512,
        causal=False,
    )


def ernie_base_config() -> dict:
    """ERNIE-3.0-base-style encoder config (BASELINE.md workload #4:
    fine-tune under sharding stage 2/3).  Same transformer geometry as
    BERT-base with segment (token-type) embeddings enabled."""
    return dict(
        vocab_size=40000,  # ERNIE zh vocab (39979) padded to 64
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position=2048,
        causal=False,
        type_vocab_size=4,
    )


def gpt_1p3b_config() -> dict:
    """GPT-3 1.3B config (BASELINE.md workload #5)."""
    return dict(
        vocab_size=50304,  # 50257 padded to a multiple of 64
        hidden_size=2048,
        num_layers=24,
        num_heads=16,
        intermediate_size=8192,
        max_position=2048,
        causal=True,
    )


class TransformerLM(Layer):
    """Transformer language model with tied input/output embeddings."""

    #: decode-cache layouts gen_decode_cache can build (the positional
    #: K/V pair — jit.cache; nn.ssm.SSMLM conversely serves only
    #: "recurrent").  DecodeSession checks this at construction.
    cache_layouts = ("dense", "paged")

    def __init__(
        self,
        vocab_size: int = 30528,
        hidden_size: int = 768,
        num_layers: int = 12,
        num_heads: int = 12,
        intermediate_size: Optional[int] = None,
        max_position: int = 512,
        dropout: float = 0.1,
        activation: str = "gelu",
        causal: bool = True,
        normalize_before: bool = True,
        type_vocab_size: int = 0,
    ):
        super().__init__()
        intermediate_size = intermediate_size or 4 * hidden_size
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.causal = causal
        self.word_embeddings = Embedding(vocab_size, hidden_size)
        self.position_embeddings = Embedding(max_position, hidden_size)
        # segment embeddings (BERT/ERNIE token types); 0 disables
        self.token_type_embeddings = (
            Embedding(type_vocab_size, hidden_size)
            if type_vocab_size else None)
        self.embed_dropout = Dropout(dropout)
        layer = TransformerEncoderLayer(
            hidden_size,
            num_heads,
            intermediate_size,
            dropout=dropout,
            activation=activation,
            normalize_before=normalize_before,
        )
        self.encoder = TransformerEncoder(layer, num_layers)
        self.final_norm = LayerNorm(hidden_size)
        self._sequence_parallel = False

    def enable_sequence_parallel(self, group=None, mode: str = "ring"):
        """Train with the sequence dim sharded over the ``sep`` mesh axis.

        Every attention block switches to ring/Ulysses attention
        (``meta_parallel/sequence_parallel.py``); causality moves from the
        materialized additive mask into the SP kernel, so no [L, L] mask is
        ever built.  Activations between blocks are per-position math that
        GSPMD shards along the sequence automatically.
        """
        for enc_layer in self.encoder.layers:
            enc_layer.self_attn.enable_sequence_parallel(
                group, mode=mode, causal=self.causal)
        self._sequence_parallel = True
        return self

    def _causal_mask(self, seq_len: int, dtype):
        # additive mask: 0 on/below diagonal, -inf above
        idx = jnp.arange(seq_len)
        allow = idx[None, :] <= idx[:, None]
        return jnp.where(allow, 0.0, jnp.finfo(jnp.float32).min).astype(dtype)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """Per-layer preallocated KV decode cache (see
        ``MultiHeadAttention.gen_decode_cache``); thread it through
        ``forward(..., cache=...)`` for O(1)-per-token generation.
        ``layout="paged"`` selects the block-table cache
        (``PagedDecodeCache``) whose HBM scales with allocated tokens;
        ``dtype="int8"`` stores K/V quantized with per-head fp32 scales
        (quantize-on-write, dequant inside the attention — docs/DESIGN.md
        §5d), cutting the bytes every decode step streams ~4x vs fp32.
        Unsupported dtypes raise a typed error naming the supported set.

        Causal models only: the cached path masks attention causally over
        the prefix, which for a bidirectional (``causal=False``) encoder
        would silently CHANGE the math rather than just the cost — and
        incremental decoding is ill-defined there anyway (every new token
        would retroactively change all earlier hidden states)."""
        if not self.causal:
            raise InvalidArgumentError(
                "decode caching requires a causal model: a "
                "causal=False (bidirectional) encoder cannot decode "
                "incrementally — new tokens would change every earlier "
                "position's hidden state")
        return self.encoder.gen_decode_cache(batch_size, max_length, dtype,
                                             per_slot, layout, block_size,
                                             num_blocks)

    def encode(self, input_ids, attn_mask=None, token_type_ids=None,
               cache=None):
        """Final hidden states [B, L, H] (the backbone for task heads).

        With ``cache`` (a ``gen_decode_cache`` pytree) the input is an
        incremental chunk: positions start at the cache index, causality
        over the cached prefix is enforced INSIDE the attention (no
        [L, L] mask is built), and ``(hidden, new_cache)`` is returned.
        """
        seq_len = input_ids.shape[1]
        if cache is not None:
            idx = jnp.asarray(cache[0].index, jnp.int32)
            step = jnp.arange(seq_len, dtype=jnp.int32)
            # scalar index -> [L]; per-slot [B] index -> [B, L]
            pos = Tensor(idx + step if idx.ndim == 0
                         else idx[:, None] + step[None, :],
                         stop_gradient=True)
        else:
            pos = T.arange(0, seq_len, dtype="int64")
        h = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if self.token_type_embeddings is not None and token_type_ids is not None:
            h = h + self.token_type_embeddings(token_type_ids)
        h = self.embed_dropout(h)
        if cache is not None:
            h, new_cache = self.encoder(h, attn_mask, cache)
            return self.final_norm(h), new_cache
        if attn_mask is None and self.causal and not self._sequence_parallel:
            attn_mask = Tensor(
                self._causal_mask(seq_len, h.value.dtype), stop_gradient=True
            )
        h = self.encoder(h, attn_mask)
        return self.final_norm(h)

    def forward(self, input_ids, attn_mask=None, token_type_ids=None,
                cache=None):
        if cache is not None:
            h, new_cache = self.encode(input_ids, attn_mask, token_type_ids,
                                       cache)
            return self._lm_head(h), new_cache
        return self._lm_head(self.encode(input_ids, attn_mask,
                                         token_type_ids))

    def _lm_head(self, h):
        # tied LM head: logits = h @ E^T.  No Layer runs here, so the
        # scope that names the vocabulary-wide matmul in a device
        # profile is opened by hand
        with jax.named_scope("lm_head"):
            return T.matmul(h, self.word_embeddings.weight,
                            transpose_y=True)

    def flops_per_token(self, seq_len: int) -> float:
        """Analytic fwd+bwd FLOPs/token for MFU accounting (PaLM appendix B).

        6 * n_params_matmul + attention term 12 * L * H * seq.
        """
        h, l, ff, v = self.hidden_size, self.num_layers, self.intermediate_size, self.vocab_size
        per_layer = 4 * h * h + 2 * h * ff  # qkvo + mlp matmul params
        matmul_params = l * per_layer + v * h  # + lm head (tied)
        attn = 12 * l * h * seq_len  # fwd+bwd qk^T and av matmuls
        return 6.0 * matmul_params + attn


class TransformerLMCriterion(Layer):
    """Next-token (or masked) LM loss with fused softmax cross-entropy."""

    def __init__(self, shift_labels: bool = True):
        super().__init__()
        self.shift_labels = shift_labels

    def forward(self, logits, labels):
        if self.shift_labels:
            logits = logits[:, :-1, :]
            labels = labels[:, 1:]
        v = logits.shape[-1]
        return F.cross_entropy(
            T.reshape(logits, [-1, v]), T.reshape(labels, [-1]), reduction="mean"
        )


class TransformerForSequenceClassification(Layer):
    """Encoder + BERT-style pooler + classifier (the ERNIE fine-tune head,
    BASELINE.md workload #4)."""

    def __init__(self, num_classes: int = 2, dropout: float = 0.1, **config):
        super().__init__()
        config.setdefault("causal", False)
        self.backbone = TransformerLM(dropout=dropout, **config)
        h = self.backbone.hidden_size
        self.pooler = Linear(h, h)
        self.classifier_dropout = Dropout(dropout)
        self.classifier = Linear(h, num_classes)

    def forward(self, input_ids, attn_mask=None, token_type_ids=None):
        hidden = self.backbone.encode(input_ids, attn_mask, token_type_ids)
        pooled = T.tanh(self.pooler(hidden[:, 0]))  # [CLS] pooling
        return self.classifier(self.classifier_dropout(pooled))
