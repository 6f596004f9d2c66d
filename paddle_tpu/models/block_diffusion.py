"""A Qwen3-style sparse-expert decoder that generates by diffusion over
blocks (the SDAR family, ``model_type: sdar_moe``).

One layer on ``h`` [L, H]::

    a = rmsnorm(h)         q, k, v = a Wq, a Wk, a Wv      (no bias)
    q, k = rmsnorm over each head's channels, then rotary positions
    o = softmax(q k^T / sqrt(D) + M) v, query head n on K/V head n // g
    h = h + concat(o) Wo
    m = rmsnorm(h)         p = softmax(m Wr) over all experts
    h = h + sum over the top_k experts e of (p_e / sum of the chosen p)
            * Wdown_e (silu(Wgate_e m) * (Wup_e m))

then a final RMSNorm and an output head of its own (not the embedding's
transpose).  ``M`` is block-causal: position ``i`` sees position ``j``
where ``j // block_length <= i // block_length``.

Generation is the pool's (``inference.BlockDiffusionPool``): a block of
``block_length`` positions starts as mask ids, a denoising step runs this
model over the block against the cache of the earlier blocks and commits
the most confident positions, and once the block is clean its K/V is
written by the forward that first denoises the NEXT block: that forward
runs the clean block and the new one side by side, ``2 * block_length``
rows under the one block-causal mask, and asks the head for the new
block's rows alone (``forward(..., last=)``).  The model declares that
through ``generation`` and ``block_length``/``mask_token_id``/
``denoise_steps``; the serving engine reads them and picks the pool.

Built from ``nn.Layer``s, so a compiled step carries the module tree as
scopes (``layers/3/self_attn/q_proj``, ``layers/3/moe/experts``,
``lm_head``).  Parameters are created in ``dtype`` (bfloat16 as released):
nothing of the released size is ever held in float32.
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
from ..nn.layer.moe import SparseExperts
from ..nn.layer.norm import RMSNorm
from ..nn.layer.transformer import GroupedQueryAttention

__all__ = ["BlockDiffusionDecoderLayer", "BlockDiffusionMoELM"]


class BlockDiffusionDecoderLayer(Layer):
    """Pre-norm attention and sparse-expert feed-forward, each added to
    the residual stream."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 expert_size, num_experts, top_k, rope_theta, norm_epsilon,
                 block_length, held_experts=None):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = GroupedQueryAttention(
            hidden_size, num_heads, num_kv_heads, head_dim,
            rope_theta=rope_theta, qk_norm=True, norm_epsilon=norm_epsilon,
            block_length=block_length)
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.moe = SparseExperts(hidden_size, expert_size, num_experts,
                                 top_k, held=held_experts)

    def forward(self, h, cache=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.self_attn(a)
        else:
            o, cache = self.self_attn(a, cache=cache)
            h = h + o
        h = h + self.moe(self.post_norm(h))
        return h if cache is None else (h, cache)

    def gen_decode_cache(self, *args, **kwargs):
        return self.self_attn.gen_decode_cache(*args, **kwargs)


class BlockDiffusionMoELM(Layer):
    """See the module docstring.  ``forward(ids, cache=None)`` gives
    logits ``[B, L, V]`` under the block-causal mask, and with a
    ``gen_decode_cache`` pytree ``(logits, new_cache)`` for the chunk at
    the cache index, as ``TransformerLM`` does.  ``last`` (with a cache;
    int32 ``[B]``, may be traced): the head runs on the ``block_length``
    rows of the chunk that start at row ``last[b]`` and on no other,
    logits ``[B, block_length, V]``: a chunk of two blocks of which one
    is denoised pays the vocabulary's product for that one."""

    #: the engine picks its pool by this (``inference.BlockDiffusionPool``)
    generation = "block_diffusion"
    cache_layouts = ("dense", "paged")
    causal = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 expert_size: int, num_experts: int, top_k: int,
                 block_length: int, mask_token_id: int,
                 denoise_steps: Optional[int] = None,
                 rope_theta: float = 1e6, norm_epsilon: float = 1e-6,
                 dtype: str = "bfloat16", held_experts=None,
                 initializer_range: float = 0.02):
        super().__init__()
        if block_length < 1:
            raise InvalidArgumentError(
                "block_length must be >= 1, got %r" % (block_length,))
        if not 0 <= mask_token_id < vocab_size:
            raise InvalidArgumentError(
                "mask_token_id %r is not an id of the vocabulary of %d"
                % (mask_token_id, vocab_size))
        steps = block_length if denoise_steps is None else int(denoise_steps)
        if not 1 <= steps <= block_length:
            raise InvalidArgumentError(
                "denoise_steps must lie in 1..block_length=%d (a step "
                "commits at least one position), got %r"
                % (block_length, denoise_steps))
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.block_length = int(block_length)
        self.mask_token_id = int(mask_token_id)
        self.denoise_steps = steps
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
                BlockDiffusionDecoderLayer(
                    hidden_size, num_heads, num_kv_heads, head_dim,
                    expert_size, num_experts, top_k, rope_theta,
                    norm_epsilon, block_length, held_experts)
                for _ in range(num_layers)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
            self.lm_head = Linear(hidden_size, vocab_size, weight_attr=init,
                                  bias_attr=False)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="bfloat16", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """Per-layer decode caches of K/V HEADS (``[.., num_kv_heads, ..,
        head_dim]``), dense or paged, as ``MultiHeadAttention
        .gen_decode_cache`` builds them.  Float caches only: an int8 cache
        has no grouped-head kernel."""
        if jnp.dtype(dtype) == jnp.int8:
            raise InvalidArgumentError(
                "BlockDiffusionMoELM keeps a float K/V cache: the int8 "
                "cache has no grouped-head attention")
        return [layer.gen_decode_cache(batch_size, max_length, dtype,
                                       per_slot, layout, block_size,
                                       num_blocks)
                for layer in self.layers]

    def encode(self, input_ids, cache=None):
        """Final normalised hidden states, ``(hidden, new_cache)`` with a
        cache.  Positions are the rotary ones inside each attention, read
        from the cache index: nothing is added here."""
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
        if cache is None:
            return self.lm_head(self.encode(input_ids))
        h, cache = self.encode(input_ids, cache)
        if last is not None:
            with jax.named_scope("lm_head"):
                rows = jnp.asarray(last, jnp.int32)[:, None] \
                    + jnp.arange(self.block_length, dtype=jnp.int32)
                h = Tensor(jnp.take_along_axis(h.value, rows[:, :, None],
                                               axis=1), stop_gradient=True)
        return self.lm_head(h), cache
