"""A decoder of compressed convolutional attention and top-1 routed experts
whose router carries a state from layer to layer (the ZAYA1 family,
``model_type: zaya``).

Every layer on the residual stream ``h`` [L, H] and the router's stream
``r`` [L, R] of the layer before::

    h = h + cca(rmsnorm(h))                       nn.CCAttention
    m = rmsnorm(h)
    s, r = router(m, r)                           nn.DepthMLPRouter
    p = softmax(s);  e = argmax p
    h = h + p_e * Wdown_e (silu(Wgate_e m) * (Wup_e m))

then a final RMSNorm and the embedding as the output head (tied).  With one
expert a token the gate is the chosen expert's probability as it stands
(``renormalise=False``: renormalised it would be 1.0).  The layers hand
``(h, r)`` on: a second, narrow residual stream through the depth of the
model.

**A layer owns two cache entries**: paged (or dense) K/V and an
``nn.CCADecodeCache`` (the convolutions' last inputs and the value half the
next position reads: ``jit.cache.RecurrentLayout``).  ``gen_decode_cache``
returns the FLAT list of ``2 x num_layers`` entries, a layer's K/V entry
then its state, and ``encode`` walks it two at a time: a flat list is what
``jit.cache.layout_of`` composes, entry by entry, so ``DecodeSession``,
``GenerationPool`` and ``ServingEngine`` serve it through the steps every
other model takes; what needs every entry to address positions (prefix
sharing, chunked prefill, speculative rewind) they refuse, naming the
recurrent entries.

``held_experts = (first, count)`` says which experts' weights live here
(default: all).  Parameters are created in ``dtype``; the router, the
convolutions' sums, the q/k statistics and every softmax are float32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dtype import get_default_dtype, set_default_dtype
from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer.cca_attention import CCAttention
from ..nn.layer.common import Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.moe import DepthMLPRouter, SparseExperts
from ..nn.layer.norm import RMSNorm

__all__ = ["CCAMoEDecoderLayer", "CCAMoELM"]


class CCAMoEDecoderLayer(Layer):
    """Pre-norm attention and routed experts, each added to the residual
    stream; ``forward(h, r)`` gives ``(h, r)``, with a cache ``(h, r,
    cache)``."""

    def __init__(self, attention: Layer, experts: Layer, hidden_size: int,
                 norm_epsilon: float):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = attention
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.moe = experts

    def forward(self, h, r=None, cache=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.self_attn(a)
        else:
            o, cache = self.self_attn(a, cache=cache)
            h = h + o
        o, r = self.moe(self.post_norm(h), r)
        h = h + o
        return (h, r) if cache is None else (h, r, cache)


class CCAMoELM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]``; with a ``gen_decode_cache`` list ``(logits, new_cache)`` for the
    positions at the cache index."""

    #: the layouts the K/V entries can take; the state entries are
    #: recurrent whichever is chosen
    cache_layouts = ("dense", "paged")
    causal = True
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 conv_taps, moe_intermediate_size: int, num_experts: int,
                 top_k: int = 1, router_hidden_size: int = 256,
                 rope_theta: float = 10000.0,
                 partial_rotary_factor: float = 1.0,
                 norm_epsilon: float = 1e-5, dtype: str = "bfloat16",
                 held_experts=None, initializer_range: float = 0.02):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            self.word_embeddings = Embedding(
                vocab_size, hidden_size,
                weight_attr=I.Normal(0.0, initializer_range))
            self.layers = LayerList([
                CCAMoEDecoderLayer(
                    CCAttention(hidden_size, num_heads, num_kv_heads,
                                head_dim, conv_taps, rope_theta,
                                int(head_dim * partial_rotary_factor)),
                    SparseExperts(
                        hidden_size, moe_intermediate_size, num_experts,
                        top_k, held=held_experts,
                        initializer_range=initializer_range,
                        renormalise=False,
                        router=DepthMLPRouter(
                            hidden_size, router_hidden_size, num_experts,
                            first=i == 0, norm_epsilon=norm_epsilon)),
                    hidden_size, norm_epsilon)
                for i in range(num_layers)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="bfloat16", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """The flat list of ``2 x num_layers`` entries: a layer's K/V entry
        in ``layout`` and ``dtype``, then its ``nn.CCADecodeCache``."""
        if layout not in self.cache_layouts:
            raise InvalidArgumentError(
                "CCAMoELM's K/V entries are kept in one of %r (its state "
                "entries are recurrent whichever is chosen); "
                "cache_layout=%r does not exist for it"
                % (self.cache_layouts, layout))
        return [entry for layer in self.layers
                for entry in layer.self_attn.gen_decode_cache(
                    batch_size, max_length, dtype, per_slot, layout,
                    block_size, num_blocks)]

    def encode(self, input_ids, cache=None):
        """Final normalised hidden states, ``(hidden, new_cache)`` with a
        cache."""
        h, r = self.word_embeddings(input_ids), None
        if cache is None:
            for layer in self.layers:
                h, r = layer(h, r)
            return self.final_norm(h)
        new = []
        for i, layer in enumerate(self.layers):
            h, r, pair = layer(h, r, cache=(cache[2 * i], cache[2 * i + 1]))
            new.extend(pair)
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
