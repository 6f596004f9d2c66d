"""A latent-attention decoder with routed experts and a shared expert (the
DeepSeek-V3 family as ``model_type: axk1`` publishes it).

Every layer on ``h`` [L, H]::

    h = h + latent_attention(rmsnorm(h))          nn.LatentAttention
    m = rmsnorm(h)
    h = h + Wdown (silu(Wgate m) * (Wup m))                the first
                                                  ``first_k_dense`` layers
    h = h + sum_e g_e E_e(m) + E_shared(m)                 every other layer

then a final RMSNorm and an output head of its own.  The router scores with
a sigmoid, keeps the best ``topk_group`` of ``n_group`` groups of
consecutive experts, takes the ``top_k`` largest scores among them and
scales the renormalised gates (``F.route_top_k``); ``E`` is the gated-SiLU
feed-forward of width ``moe_intermediate_size``, the shared expert
``n_shared_experts`` times as wide.

**One chip's share of a deployment.**  ``held_experts = (first, count)``
says which routed experts' weights live here (the router still scores all
``num_experts``: a token routed elsewhere adds nothing here, its holder adds
it), and ``vocab_size`` is the number of rows of the embedding and of the
head held here (a slice of the published vocabulary: ids and logits are the
slice's).  The shared expert is computed by every holder.

**Its decode cache is a latent a layer** (``nn.PagedLatentDecodeCache`` or
``nn.LatentDecodeCache``; ``jit.cache.LatentLayout``): ``kv_lora_rank +
qk_rope_head_dim`` values a position, whatever the number of heads.  A chunk
against the cache runs the absorbed form, a prompt from position 0 the
expanded form: the layer chooses by where the chunk is known to start.  ``DecodeSession``,
``GenerationPool`` and ``ServingEngine`` serve it through the steps every
other model takes.

Parameters are created in ``dtype``; the router's scores, the softmax and
the norms' statistics are float32.
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
from ..nn.layer.latent_attention import LatentAttention
from ..nn.layer.layers import Layer
from ..nn.layer.moe import SparseExperts
from ..nn.layer.norm import RMSNorm
from ..nn.layer.transformer import GatedMLP

__all__ = ["LatentMoEDecoderLayer", "LatentMoELM"]


class LatentMoEDecoderLayer(Layer):
    """Pre-norm latent attention and a feed-forward (``mlp``: dense, or
    ``moe``: routed experts with a shared one), each added to the residual
    stream."""

    def __init__(self, attention: Layer, feed_forward: Layer, dense: bool,
                 hidden_size: int, norm_epsilon: float):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = attention
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.dense = bool(dense)
        if dense:
            self.mlp = feed_forward
        else:
            self.moe = feed_forward

    def forward(self, h, cache=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.self_attn(a)
        else:
            o, cache = self.self_attn(a, cache=cache)
            h = h + o
        m = self.post_norm(h)
        h = h + (self.mlp(m) if self.dense else self.moe(m))
        return h if cache is None else (h, cache)


class LatentMoELM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]`` over the ``vocab_size`` rows held; with a ``gen_decode_cache``
    pytree ``(logits, new_cache)`` for the positions at the cache index."""

    cache_layouts = ("dense", "paged")
    causal = True
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, intermediate_size: int,
                 moe_intermediate_size: int, num_experts: int, top_k: int,
                 n_group: int = 1, topk_group: int = 1,
                 routed_scaling_factor: float = 1.0,
                 n_shared_experts: int = 1, first_k_dense: int = 1,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None,
                 norm_epsilon: float = 1e-6, dtype: str = "bfloat16",
                 held_experts=None, initializer_range: float = 0.02):
        super().__init__()
        if not 0 <= first_k_dense <= num_layers:
            raise InvalidArgumentError(
                "first_k_dense %d is not a number of the %d layers"
                % (first_k_dense, num_layers))
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers, self.num_heads = int(num_layers), int(num_heads)
        self.first_k_dense = int(first_k_dense)
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            init = I.Normal(0.0, initializer_range)
            self.word_embeddings = Embedding(vocab_size, hidden_size,
                                             weight_attr=init)

            def feed_forward(dense: bool):
                if dense:
                    return GatedMLP(hidden_size, intermediate_size)
                return SparseExperts(
                    hidden_size, moe_intermediate_size, num_experts, top_k,
                    held=held_experts,
                    initializer_range=initializer_range,
                    scoring="sigmoid", n_group=n_group,
                    topk_group=topk_group,
                    routed_scale=routed_scaling_factor,
                    shared_size=n_shared_experts * moe_intermediate_size)

            self.layers = LayerList([
                LatentMoEDecoderLayer(
                    LatentAttention(
                        hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                        qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                        rope_theta, rope_scaling, norm_epsilon),
                    feed_forward(i < first_k_dense), i < first_k_dense,
                    hidden_size, norm_epsilon)
                for i in range(num_layers)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
            self.lm_head = Linear(hidden_size, vocab_size, weight_attr=init,
                                  bias_attr=False)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="bfloat16", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """One latent entry a layer, by slot or paged (``nn.LatentAttention
        .gen_decode_cache``)."""
        if layout not in self.cache_layouts:
            raise InvalidArgumentError(
                "LatentMoELM keeps its latents in one of %r; "
                "cache_layout=%r does not exist for it"
                % (self.cache_layouts, layout))
        return [layer.self_attn.gen_decode_cache(
                    batch_size, max_length, dtype, per_slot, layout,
                    block_size, num_blocks)
                for layer in self.layers]

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
