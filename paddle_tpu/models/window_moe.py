"""A sparse-expert decoder whose layers mix WINDOW and GLOBAL attention and
whose router reads the layer's input (the SmallThinker family,
``model_type: smallthinker``).

One layer ``l`` on ``h`` [L, H]::

    r = h Wr                    float32: the router reads the layer's INPUT,
                                the residual stream before the first norm
    a = rmsnorm(h)              q, k, v = a Wq, a Wk, a Wv   (no bias, no
                                                              QK-norm)
    rope_layout[l] = 1: q, k turned by rotary positions; 0: no position term
    o_i = softmax_j(q_i k_j / sqrt(D)) v_j  over j <= i, and where
          sliding_window_layout[l] = 1 over i - window < j <= i
    h = h + concat(o) Wo;       m = rmsnorm(h)
    E = the top_k largest of r; g = softmax of r over all experts,
                                renormalised over E
    h = h + sum over e in E of g_e * Wdown_e (relu(Wgate_e m) * (Wup_e m))

then a final RMSNorm and an output head of its own.  The two layouts are
lists of 0 / 1 a layer, as the published config has them: a layer's KIND is
read from them and nothing else says it.  A window layer's decode cache
under the paged layout is a ring of blocks (``nn.GroupedQueryAttention
(window=)``, ``jit.cache.WindowLayout``); a global layer's is on the block
table like any other model's, so ``gen_decode_cache`` hands out a list that
mixes the two kinds and ``jit.cache.layout_of`` composes it.

Not built: the family's secondary experts and its activation-sparsity
predictors (``relu`` is computed in full, zeros and all).

Built from ``nn.Layer``s, so a compiled step carries the module tree as
scopes: ``layers/3/moe/router`` (BEFORE ``layers/3/self_attn``),
``layers/3/self_attn/paged_attn/window``, ``layers/3/moe/experts``,
``lm_head``.  Parameters are created in ``dtype``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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

__all__ = ["WindowMoEDecoderLayer", "WindowMoELM"]

# The most rows one call of the experts takes.  A prompt's rows go through
# them this many at a time, one after the other: the grouped matmuls keep
# ``rows x top_k`` pairs of the stream's width and of the experts' (in and
# out, some in float32), 2.2 GB at 12,288 rows of width 2,560 with 6 experts
# a row, beside 11 GB of weights on a chip of 16 (PERF.md section 6, PR 50:
# the whole prompt at once did not load there).  4,096 rows are 384 a
# expert at 64 experts: the matmuls stay whole tiles.
EXPERT_ROWS = 2048


class WindowMoEDecoderLayer(Layer):
    """The router's product on the layer's input, pre-norm attention
    (windowed or global, with or without rotary positions), and the
    ReLU-gated experts under the scores computed first."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 expert_size, num_experts, top_k, window, rope_theta,
                 norm_epsilon, held_experts=None,
                 initializer_range: float = 0.02):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = GroupedQueryAttention(
            hidden_size, num_heads, num_kv_heads, head_dim,
            rope_theta=rope_theta, qk_norm=False, norm_epsilon=norm_epsilon,
            window=window)
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.moe = SparseExperts(hidden_size, expert_size, num_experts,
                                 top_k, held=held_experts,
                                 initializer_range=initializer_range,
                                 scoring="softmax", renormalise=True,
                                 activation="relu")

    def forward(self, h, cache=None):
        # the router reads the residual stream as the layer receives it
        with jax.named_scope("moe"):
            scores = self.moe.scores_of(h)
        a = self.input_norm(h)
        if cache is None:
            h = h + self.self_attn(a)
        else:
            o, cache = self.self_attn(a, cache=cache)
            h = h + o
        h = h + self._experts(self.post_norm(h), scores)
        return h if cache is None else (h, cache)

    def _experts(self, m, scores):
        """The experts' sum for the rows of ``m`` ``[B, L, H]`` under
        ``scores`` ``[B * L, E]``: in one call, or where there are more
        than ``EXPERT_ROWS`` rows (a long prompt) in whole runs of
        ``EXPERT_ROWS`` one after the other.  A row's sum is its own
        whichever rows go with it."""
        rows = m.shape[0] * m.shape[1]
        if rows <= EXPERT_ROWS or rows % EXPERT_ROWS:
            return self.moe(m, scores=scores)
        runs = rows // EXPERT_ROWS
        out = jax.lax.map(
            lambda run: self.moe(Tensor(run[0], stop_gradient=True),
                                 scores=run[1]).value,
            (m.value.reshape(runs, EXPERT_ROWS, m.shape[-1]),
             scores.reshape(runs, EXPERT_ROWS, scores.shape[-1])))
        return Tensor(out.reshape(m.shape), stop_gradient=True)


class WindowMoELM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]``; with a ``gen_decode_cache`` list ``(logits, new_cache)`` for the
    positions at the cache index.  ``sliding_window_layout`` and
    ``rope_layout``: a 0 / 1 a layer (1: a window of ``window`` positions;
    1: rotary positions at ``rope_theta``)."""

    cache_layouts = ("dense", "paged")
    causal = True
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 expert_size: int, num_experts: int, top_k: int,
                 window: int, sliding_window_layout: Sequence[int],
                 rope_layout: Sequence[int], rope_theta: float = 1.5e6,
                 norm_epsilon: float = 1e-6, dtype: str = "bfloat16",
                 held_experts=None, initializer_range: float = 0.02):
        super().__init__()
        for name, layout in (("sliding_window_layout", sliding_window_layout),
                             ("rope_layout", rope_layout)):
            if len(layout) != num_layers \
                    or any(int(x) not in (0, 1) for x in layout):
                raise InvalidArgumentError(
                    "%s is a 0 or a 1 for each of the %d layers, got %r"
                    % (name, num_layers, list(layout)))
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.window = int(head_dim), int(window)
        self.sliding_window_layout = [int(x) for x in sliding_window_layout]
        self.rope_layout = [int(x) for x in rope_layout]
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            init = I.Normal(0.0, initializer_range)
            self.word_embeddings = Embedding(vocab_size, hidden_size,
                                             weight_attr=init)
            self.layers = LayerList([
                WindowMoEDecoderLayer(
                    hidden_size, num_heads, num_kv_heads, head_dim,
                    expert_size, num_experts, top_k,
                    window if windowed else None,
                    rope_theta if turned else None, norm_epsilon,
                    held_experts, initializer_range)
                for windowed, turned in zip(self.sliding_window_layout,
                                            self.rope_layout)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
            self.lm_head = Linear(hidden_size, vocab_size, weight_attr=init,
                                  bias_attr=False)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="bfloat16", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """One entry a layer, each of its layer's kind: under the paged
        layout a window layer's is a ring (``nn.GroupedQueryAttention
        .gen_decode_cache``), a global layer's a block table over
        ``num_blocks``.  A single sequence's index is a numpy zero in EVERY
        entry, so a prompt is known to be one while the program is traced
        and a global layer's prompt takes the flash kernel too."""
        if layout not in self.cache_layouts:
            raise InvalidArgumentError(
                "WindowMoELM's K/V entries are kept in one of %r; "
                "cache_layout=%r does not exist for it"
                % (self.cache_layouts, layout))
        cache = [layer.self_attn.gen_decode_cache(
            batch_size, max_length, dtype, per_slot, layout, block_size,
            num_blocks) for layer in self.layers]
        if not per_slot:
            cache = [c._replace(index=np.zeros((), np.int32)) for c in cache]
        return cache

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
