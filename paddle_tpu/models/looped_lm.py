"""A decoder whose stack of layers runs several times on shared weights
(the LoopLM family, ``model_type: ouro``: "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741).

On ``h`` [L, H], the SAME ``num_layers`` layers' weights in every pass::

    h_0 = embedding(ids)
    for r in 0..R-1:                                R = total_ut_steps
        for l in 0..num_layers-1:
            a = rmsnorm_in1[l](h);  q,k,v = a Wq, a Wk, a Wv      no bias, no QK-norm
            q,k = rotary(q,k; theta, halves rotated, position i)  the same positions in every pass
            o = softmax(q K_(r,l)^T / sqrt(D) + causal) V_(r,l)   K/V of pass r, layer l: its OWN plane
            h = h + rmsnorm_in2[l](o Wo)                          sandwich: a norm AFTER the sub-layer too
            m = rmsnorm_post1[l](h)
            h = h + rmsnorm_post2[l](Wdown (silu(Wgate m) * (Wup m)))
        h = final_norm(h)                           after EVERY pass; the normalised h enters pass r+1
        s_r = h;   lambda_r = sigmoid(h w_gate + b_gate)          early_exit_gate: Linear(H, 1)
    p_r = lambda_r * prod_{j<r}(1 - lambda_j) for r < R-1;  p_{R-1} = prod_{j<R-1}(1 - lambda_j)
    exit = first r with sum_{j<=r} p_j >= early_exit_threshold, else R-1
    logits = s_exit Whead                           untied head

**A layer owns ``R`` K/V planes in ONE cache entry**, one a pass, side by
side on the entry's head axis (``nn.MultiHeadAttention.gen_decode_cache(
planes=R)``), under one block table and one index: a position's planes lie
in the same blocks, so the allocator, the splice, the table mask, preempt
and resume move them together and count what they counted, and a block's
bytes are ``R`` times a plane's.  ``gen_decode_cache`` returns
``num_layers`` entries of the ordinary dense or paged K/V type.

**The passes are a loop inside the compiled step**: ``encode`` runs
``lax.fori_loop(0, R, pass_body, ...)`` with the layers unrolled in the
body ONCE, the pass index traced, the entries' K/V carried through the
loop and updated where they lie (a pass writes and attends plane ``r`` by
a head offset; nothing of a plane's size is sliced or copied).  A step
holds ``num_layers`` layer bodies, not ``R x num_layers``.

**Every pass always runs** (as in the release): the threshold chooses
which pass's state the head reads, it saves no compute.  At the published
threshold 1.0 the cumulative exit mass reaches 1 only at the last pass,
so the logits are the last pass's; the rule is built as written, a select
among the ``R`` stored states.  With ``total_ut_steps = 1`` the one pass
carries all the mass whatever the gate says: a plain sandwich-norm
decoder through the same code.  The exit distribution stays on the
device.

Parameters are created in ``dtype``; the norms' statistics, the softmax,
the rotary turn, the gate's sigmoid and the exit distribution are float32.
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
from ..nn.layer.transformer import GatedMLP, GroupedQueryAttention

__all__ = ["LoopedDecoderLayer", "LoopedLM", "exit_pass"]


def exit_pass(gates, threshold: float):
    """The pass each position exits at, ``[...]`` int32, from the gates'
    ``lambda`` ``[R, ...]`` float32 (the module docstring's rule): the
    first ``r`` whose cumulative exit mass reaches ``threshold``, else the
    last pass.  The last pass takes whatever mass is left, its own gate
    unread."""
    r = gates.shape[0]
    # prod_{j<r}(1 - lambda_j), r = 0..R-1: what has not left before pass r
    staying = jnp.concatenate(
        [jnp.ones_like(gates[:1]), jnp.cumprod(1.0 - gates[:-1], axis=0)],
        axis=0)
    p = jnp.concatenate([gates[:-1] * staying[:-1], staying[-1:]], axis=0)
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0),
                     jnp.argmax(reached, axis=0), r - 1).astype(jnp.int32)


class LoopedDecoderLayer(Layer):
    """Attention and a gated feed-forward, each between two norms (one
    before the sub-layer, one on its output before the residual sum)."""

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, intermediate_size: int, rope_theta: float,
                 norm_epsilon: float):
        super().__init__()
        self.input_norm = RMSNorm(hidden_size, norm_epsilon)
        self.self_attn = GroupedQueryAttention(
            hidden_size, num_heads, num_kv_heads, head_dim,
            rope_theta=rope_theta, qk_norm=False)
        self.attn_out_norm = RMSNorm(hidden_size, norm_epsilon)
        self.post_norm = RMSNorm(hidden_size, norm_epsilon)
        self.mlp = GatedMLP(hidden_size, intermediate_size)
        self.mlp_out_norm = RMSNorm(hidden_size, norm_epsilon)

    def forward(self, h, cache=None, plane=None):
        a = self.input_norm(h)
        if cache is None:
            h = h + self.attn_out_norm(self.self_attn(a))
        else:
            o, cache = self.self_attn(a, cache=cache, plane=plane)
            h = h + self.attn_out_norm(o)
        h = h + self.mlp_out_norm(self.mlp(self.post_norm(h)))
        return h if cache is None else (h, cache)


class LoopedLM(Layer):
    """See the module docstring.  ``forward(ids)`` gives logits ``[B, L,
    V]``; with a ``gen_decode_cache`` list ``(logits, new_cache)`` for the
    positions at the cache index."""

    cache_layouts = ("dense", "paged")
    causal = True
    logits_at = True

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 intermediate_size: int, total_ut_steps: int = 4,
                 early_exit_threshold: float = 1.0,
                 rope_theta: float = 1000000.0, norm_epsilon: float = 1e-6,
                 dtype: str = "bfloat16", initializer_range: float = 0.02):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.total_ut_steps = int(total_ut_steps)
        if self.total_ut_steps < 1:
            raise InvalidArgumentError(
                "total_ut_steps is the number of passes through the "
                "stack, at least 1; got %r" % (total_ut_steps,))
        self.early_exit_threshold = float(early_exit_threshold)
        #: K/V planes a cache entry holds: one a pass (what the pools'
        #: byte figures, gauges and fingerprint count)
        self.cache_planes = self.total_ut_steps
        was = get_default_dtype()
        set_default_dtype(dtype)
        try:
            self.word_embeddings = Embedding(
                vocab_size, hidden_size,
                weight_attr=I.Normal(0.0, initializer_range))
            self.layers = LayerList([
                LoopedDecoderLayer(hidden_size, num_heads, num_kv_heads,
                                   head_dim, intermediate_size, rope_theta,
                                   norm_epsilon)
                for _ in range(num_layers)])
            self.final_norm = RMSNorm(hidden_size, norm_epsilon)
            self.early_exit_gate = Linear(
                hidden_size, 1, weight_attr=I.Normal(0.0, initializer_range))
            self.lm_head = Linear(
                hidden_size, vocab_size,
                weight_attr=I.Normal(0.0, initializer_range),
                bias_attr=False)
        finally:
            set_default_dtype(was)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="bfloat16", per_slot: bool = False,
                         layout: str = "dense", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """``num_layers`` K/V entries in ``layout`` and ``dtype``, each of
        ``total_ut_steps`` planes (int8 is refused, naming the planes)."""
        if layout not in self.cache_layouts:
            raise InvalidArgumentError(
                "LoopedLM keeps K/V in one of %r; cache_layout=%r does "
                "not exist for it" % (self.cache_layouts, layout))
        return [layer.self_attn.gen_decode_cache(
                    batch_size, max_length, dtype, per_slot, layout,
                    block_size, num_blocks, planes=self.cache_planes)
                for layer in self.layers]

    def _one_pass(self, r, h, payload, cache):
        """The stack once on ``h`` (raw ``[B, L, H]``), as pass ``r``:
        ``(h after the final norm, the entries' new K/V, lambda_r)``.
        ``payload`` is every entry's ``(k, v)``; its ``table`` and
        ``index`` are ``cache``'s, the same in every pass."""
        new = []
        with jax.named_scope("pass"):
            h = Tensor(h, stop_gradient=True)
            if cache is None:
                for layer in self.layers:
                    h = layer(h)
            else:
                for layer, c, (k, v) in zip(self.layers, cache, payload):
                    h, c = layer(h, cache=c._replace(k=k, v=v), plane=r)
                    new.append((c.k, c.v))
        h = self.final_norm(h)
        with jax.named_scope("exit_gate"):
            gate = jax.nn.sigmoid(
                self.early_exit_gate(h).value[..., 0].astype(jnp.float32))
        return h.value, new, gate

    def encode(self, input_ids, cache=None):
        """The exit pass's normalised hidden states, ``(hidden,
        new_cache)`` with a cache.  One ``lax.fori_loop`` over the passes
        with or without a cache."""
        passes = self.total_ut_steps
        h = self.word_embeddings(input_ids).value
        payload = [] if cache is None else [(c.k, c.v) for c in cache]

        def pass_body(r, carry):
            h, payload, states, gates = carry
            h, payload, gate = self._one_pass(r, h, payload, cache)
            return (h, payload, states.at[r].set(h), gates.at[r].set(gate))

        with jax.named_scope("loop"):
            _, payload, states, gates = jax.lax.fori_loop(
                0, passes, pass_body,
                (h, payload, jnp.zeros((passes,) + h.shape, h.dtype),
                 jnp.zeros((passes,) + h.shape[:-1], jnp.float32)))
            with jax.named_scope("exit_select"):
                at = exit_pass(gates, self.early_exit_threshold)
                h = jnp.take_along_axis(states, at[None, ..., None],
                                        axis=0)[0]
        h = Tensor(h, stop_gradient=True)
        if cache is None:
            return h
        length = input_ids.shape[1]
        return h, [c._replace(k=k, v=v,
                              index=jnp.asarray(c.index, jnp.int32) + length)
                   for c, (k, v) in zip(cache, payload)]

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
