"""An attention-free decoder whose every layer keeps a gated power-retention
state instead of K/V (the ``brumby`` family: a Qwen3 dense block retrained
with the softmax product replaced by power retention).

One layer on ``h`` [L, H]::

    a = rmsnorm(h)         q, k, v = a Wq, a Wk, a Wv      (no bias)
    q, k = rmsnorm over each head's channels, then rotary positions
    lg = log_sigmoid(a Wg + bg), one gate a K/V head, float32
    y  = power retention of degree 2 (nn.PowerRetention), query head n on
         K/V head n // g
    h = h + concat(y) Wo
    h = h + Wdown (silu(Wgate m) * (Wup m))      m = rmsnorm(h)

then a final RMSNorm and an output head of its own.  Its decode cache is
the ``recurrent`` layout's (``nn.RetentionDecodeCache``, float32, of
constant size whatever the context), so ``DecodeSession``,
``GenerationPool`` and ``ServingEngine`` serve it through the steps the
attention models take; what needs positions to address (prefix sharing,
chunked prefill, speculative rewind) they refuse by the layout's name.

Parameters are created in ``dtype`` (bfloat16 as released); the state is
float32 always.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dtype import get_default_dtype, set_default_dtype
from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.layer.retention import PowerRetention
from ..nn.layer.transformer import GatedMLP

__all__ = ["PowerRetentionDecoderLayer", "PowerRetentionLM"]


class PowerRetentionDecoderLayer(Layer):
    """Pre-norm power retention and a dense gated feed-forward, each added
    to the residual stream."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 intermediate_size, rope_theta, norm_epsilon):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = PowerRetention(
            hidden_size, num_heads, num_kv_heads, head_dim,
            rope_theta=rope_theta, qk_norm=True, norm_epsilon=norm_epsilon)
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.mlp = GatedMLP(hidden_size, intermediate_size)

    def forward(self, h, cache=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.self_attn(a)
        else:
            o, cache = self.self_attn(a, cache=cache)
            h = h + o
        h = h + self.mlp(self.post_norm(h))
        return h if cache is None else (h, cache)


class PowerRetentionLM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]``; with a ``gen_decode_cache`` pytree ``(logits, new_cache)`` for the
    positions at the cache index, as ``TransformerLM`` does."""

    cache_layouts = ("recurrent",)
    causal = True
    #: ``forward(..., last=p)`` runs the head on position ``p`` alone: a
    #: bucketed prefill (``jit.DecodeSession._prefill``) asks for that
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 intermediate_size: int, rope_theta: float = 1e6,
                 norm_epsilon: float = 1e-6, dtype: str = "bfloat16",
                 initializer_range: float = 0.02):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        # every sublayer creates its parameters in the default float type
        # of the moment: make that ``dtype`` while they are built, so the
        # released size never exists in float32
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            init = I.Normal(0.0, initializer_range)
            self.word_embeddings = Embedding(vocab_size, hidden_size,
                                             weight_attr=init)
            self.layers = LayerList([
                PowerRetentionDecoderLayer(
                    hidden_size, num_heads, num_kv_heads, head_dim,
                    intermediate_size, rope_theta, norm_epsilon)
                for _ in range(num_layers)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
            self.lm_head = Linear(hidden_size, vocab_size, weight_attr=init,
                                  bias_attr=False)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "recurrent", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """One ``nn.RetentionDecodeCache`` a layer: float32, of constant
        size.  Only ``layout='recurrent'`` exists for this model."""
        if layout != "recurrent":
            raise InvalidArgumentError(
                "PowerRetentionLM keeps a retention state of constant size, "
                "not positional K/V: cache_layout=%r does not exist for "
                "this model class; construct the session/pool with "
                "cache_layout='recurrent'" % (layout,))
        return [layer.self_attn.gen_decode_cache(batch_size, max_length,
                                                 dtype, per_slot, layout)
                for layer in self.layers]

    @staticmethod
    def prefill_chunks(length: int) -> int:
        """Chunks of positions between two updates of the state in a
        prefill of ``length`` positions from an empty state
        (``ops.power_retention.STATE_CHUNK``)."""
        from ..ops.power_retention import STATE_CHUNK

        return -(-int(length) // min(int(length), STATE_CHUNK))

    def encode(self, input_ids, cache=None):
        """Final normalised hidden states, ``(hidden, new_cache)`` with a
        cache."""
        h = self.word_embeddings(input_ids)
        if cache is None:
            for layer in self.layers:
                h = layer(h)
            return self.final_norm(h)
        new = []
        for layer, c in zip(self.layers, cache):
            h, c = layer(h, cache=c)
            new.append(c)
        return self.final_norm(h), new

    def forward(self, input_ids, cache=None, last=None):
        """``last`` (a position of the chunk, one for every row): logits
        ``[B, 1, V]`` of that position alone."""
        if cache is None:
            return self.lm_head(self.encode(input_ids))
        h, cache = self.encode(input_ids, cache)
        if last is not None:
            h = Tensor(jax.lax.dynamic_slice_in_dim(
                h.value, jnp.asarray(last, jnp.int32), 1, axis=1),
                stop_gradient=True)
        return self.lm_head(h), cache
