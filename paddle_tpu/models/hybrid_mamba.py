"""A decoder that mixes Mamba layers and attention layers in one stack (the
``jamba`` family: one attention layer every ``attn_layer_period`` layers,
at ``attn_layer_offset``; every other layer a Mamba-1 mixer).

One layer on ``h`` [L, H]::

    h = h + mixer(rmsnorm(h))         nn.MambaMixer, or grouped-query
                                      attention with NO position term (the
                                      Mamba layers carry position)
    h = h + Wdown (silu(Wgate m) * (Wup m))      m = rmsnorm(h)

then a final RMSNorm and the embedding as the output head (tied).

**Its decode cache has two kinds of entry in one list**: a Mamba layer
keeps ``nn.MambaDecodeCache`` (a convolution state and a selective-scan
state of constant size, ``jit.cache.RecurrentLayout``), an attention layer
keeps K/V, dense or paged (``cache_layouts`` names the K/V layouts: that is
what ``cache_layout=`` chooses for this model).  ``jit.cache.layout_of``
composes the entries' layouts, so ``DecodeSession``, ``GenerationPool`` and
``ServingEngine`` serve it through the steps every other model takes; what
needs every layer to address positions (prefix sharing, chunked prefill,
speculative rewind) they refuse, naming the recurrent layers.

Parameters are created in ``dtype``; the norms' scales, ``A_log``, ``D``,
``b_dt`` and the scan state are float32 always.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dtype import get_default_dtype, set_default_dtype
from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer.common import Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.mamba import MambaMixer, _f32_norm
from ..nn.layer.transformer import GatedMLP, GroupedQueryAttention

__all__ = ["HybridMambaDecoderLayer", "HybridMambaLM"]


class HybridMambaDecoderLayer(Layer):
    """Pre-norm mixer (``mixer``: Mamba or attention) and a dense gated
    feed-forward, each added to the residual stream."""

    def __init__(self, mixer: Layer, hidden_size: int,
                 intermediate_size: int, norm_epsilon: float):
        super().__init__()
        self.input_norm = _f32_norm(hidden_size, norm_epsilon)
        self.mixer = mixer
        self.post_norm = _f32_norm(hidden_size, norm_epsilon)
        self.mlp = GatedMLP(hidden_size, intermediate_size)

    def forward(self, h, cache=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.mixer(a)
        else:
            o, cache = self.mixer(a, cache=cache)
            h = h + o
        h = h + self.mlp(self.post_norm(h))
        return h if cache is None else (h, cache)


class HybridMambaLM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]``; with a ``gen_decode_cache`` pytree ``(logits, new_cache)`` for the
    positions at the cache index, as ``TransformerLM`` does."""

    #: the layouts the ATTENTION layers' K/V can take; the Mamba layers'
    #: states are recurrent whichever is chosen
    cache_layouts = ("dense", "paged")
    causal = True
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 intermediate_size: int, attn_layer_period: int = 8,
                 attn_layer_offset: int = 4, mamba_expand: int = 2,
                 mamba_d_state: int = 16, mamba_d_conv: int = 4,
                 mamba_dt_rank: int = 160, norm_epsilon: float = 1e-6,
                 dtype: str = "bfloat16", initializer_range: float = 0.02):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        if not 0 <= attn_layer_offset < attn_layer_period:
            raise InvalidArgumentError(
                "attn_layer_offset %d is not a layer of a period of %d"
                % (attn_layer_offset, attn_layer_period))
        #: layer ``i`` is an attention layer
        self.attention_layers = tuple(
            i % attn_layer_period == attn_layer_offset
            for i in range(self.num_layers))
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            self.word_embeddings = Embedding(
                vocab_size, hidden_size,
                weight_attr=I.Normal(0.0, initializer_range))
            self.layers = LayerList([
                HybridMambaDecoderLayer(
                    GroupedQueryAttention(
                        hidden_size, num_heads, num_kv_heads, head_dim,
                        rope_theta=None, qk_norm=False)
                    if attention else
                    MambaMixer(hidden_size, mamba_expand * hidden_size,
                               mamba_d_state, mamba_d_conv, mamba_dt_rank,
                               norm_epsilon),
                    hidden_size, intermediate_size, norm_epsilon)
                for attention in self.attention_layers])
            self.final_norm = _f32_norm(hidden_size, norm_epsilon)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """One entry a layer, of the layer's own kind: K/V in ``layout`` and
        ``dtype`` for an attention layer, ``nn.MambaDecodeCache`` for a
        Mamba layer."""
        if layout not in self.cache_layouts:
            raise InvalidArgumentError(
                "HybridMambaLM's attention layers keep K/V in one of %r "
                "(its Mamba layers keep a recurrent state whichever is "
                "chosen); cache_layout=%r does not exist for it"
                % (self.cache_layouts, layout))
        return [layer.mixer.gen_decode_cache(
                    batch_size, max_length, dtype, per_slot, layout,
                    block_size, num_blocks)
                for layer in self.layers]

    @staticmethod
    def prefill_chunks(length: int) -> int:
        """Blocks of positions the prefill's scan takes one after the other
        with the state kept in VMEM between them
        (``ops.selective_scan.scan_block``)."""
        from ..ops.selective_scan import scan_block

        return int(length) // scan_block(int(length))

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

    def _lm_head(self, h):
        with jax.named_scope("lm_head"):
            return Tensor(jnp.matmul(h.value,
                                     self.word_embeddings.weight.value.T),
                          stop_gradient=True)

    def forward(self, input_ids, cache=None, last=None):
        """``last`` (a position of the chunk, one for every row): logits
        ``[B, 1, V]`` of that position alone."""
        if cache is None:
            return self._lm_head(self.encode(input_ids))
        h, cache = self.encode(input_ids, cache)
        if last is not None:
            h = Tensor(jax.lax.dynamic_slice_in_dim(
                h.value, jnp.asarray(last, jnp.int32), 1, axis=1),
                stop_gradient=True)
        return self._lm_head(h), cache
