"""Gated linear-recurrence (SSD/Mamba-2-style) decoder blocks: the
O(1)-cache model class.

The serving stack's decode cache is a compiler-visible pytree contract
(``gen_decode_cache(layout=...)`` → ``jit.cache.CacheLayout``); this
module adds the model class the ``"recurrent"`` layout exists for — a
decoder whose per-token state is a CONSTANT ``[B, d_state]`` carry per
layer instead of an O(seq) attention prefix (the "Compiler-First State
Space Duality and Portable O(1) Autoregressive Caching" direction in
PAPERS.md).  No block table, no paging, no prefix tree: a slot's entire
decode state is ``layers × d_state`` floats, so the same engine serves
radically more concurrent slots per GB of HBM.

The recurrence is the diagonal gated form (the state-space-duality
"scalar SSM" / gated-linear-recurrence family — Mamba-2's SSD with a
per-channel decay, GLA/HGRN's gating shape):

    a_t = sigmoid(x_t W_a + b_a)            per-channel decay in (0, 1)
    u_t = x_t W_in + b_in                   candidate state
    s_t = a_t ⊙ s_{t-1} + (1 − a_t) ⊙ u_t   the O(1) carry
    y_t = (s_t ⊙ silu(x_t W_g + b_g)) W_out e(output gate + projection)

run as a SEQUENTIAL ``lax.scan`` rather than the O(log L) associative
scan: serving's correctness gate is byte-identity between the bucketed
prefill, the per-token decode step and an eager reference loop, and
only the sequential form makes all three reduce in the SAME fp32
operation order.  (Prefill cost is O(L·d_state) either way — the scan
body is two multiplies and an add per channel; the matmuls dominate.)

Padded-bucket discipline: a positional K/V cache may write garbage for
pad positions because its index keeps them from ever being attended; a
recurrence folds every update into the carry FOREVER.  The cache
therefore carries a ``limit`` — positions ``>= limit`` are identity
steps (``s_t = s_{t-1}``) — which the session's prefill narrows to the
true prompt length and re-opens to ``max_len`` for decode
(``jit.cache.RecurrentLayout.begin_prefill``/``finalize_prefill``).
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor
from .layer.common import Dropout, Embedding, Linear
from .layer.container import LayerList
from .layer.layers import Layer
from .layer.norm import LayerNorm

__all__ = ["RecurrentDecodeCache", "GatedSSMBlock", "SSMLM"]


#: One layer's decode state: ``state [B, d_state]`` (the fp32 carry),
#: ``index`` (positions consumed so far — scalar for aligned batches,
#: ``[B]`` per-slot for the pool, exactly the positional layouts'
#: convention) and ``limit`` (scalar update-window bound; see module
#: docstring).  The pytree the ``"recurrent"`` ``CacheLayout`` places,
#: splices, freezes, spills and fingerprints.
RecurrentDecodeCache = collections.namedtuple(
    "RecurrentDecodeCache", ["state", "index", "limit"])


class GatedSSMBlock(Layer):
    """Pre-norm gated linear-recurrence block with a residual path."""

    def __init__(self, hidden_size: int, d_state: int,
                 dropout: float = 0.0):
        super().__init__()
        self.hidden_size = int(hidden_size)
        self.d_state = int(d_state)
        self.norm = LayerNorm(hidden_size)
        self.in_proj = Linear(hidden_size, d_state)
        self.decay_proj = Linear(hidden_size, d_state)
        self.gate_proj = Linear(hidden_size, d_state)
        self.out_proj = Linear(d_state, hidden_size)
        self.out_dropout = Dropout(dropout)

    def forward(self, x, cache: Optional[RecurrentDecodeCache] = None):
        """``[B, L, H] -> [B, L, H]`` (+ successor cache when given).

        Without ``cache``: a full forward from zero state over the
        exact sequence (the eager-reference / training path).  With
        ``cache``: the chunk continues from the carry — ``L == 1`` is
        the serving decode step, larger ``L`` the bucketed prefill
        (whose pad tail the ``limit`` window turns into identity
        steps).
        """
        h = self.norm(x)
        u = self.in_proj(h).value
        a = jax.nn.sigmoid(self.decay_proj(h).value)
        g = jax.nn.silu(self.gate_proj(h).value)
        length = u.shape[1]
        if cache is None:
            s0 = jnp.zeros((u.shape[0], self.d_state), u.dtype)
            idx = limit = None
        else:
            s0, idx, limit = cache.state, cache.index, cache.limit

        def step(s, inputs):
            a_t, u_t, t = inputs
            s_new = a_t * s + (1.0 - a_t) * u_t
            if limit is not None:
                # positions past the window are identity steps: the
                # carry at the end of a padded bucket equals the carry
                # at the true prompt length
                pos = jnp.asarray(idx, jnp.int32) + t
                keep = pos < limit
                if keep.ndim:  # per-slot [B] index -> per-row window
                    keep = keep[:, None]
                s_new = jnp.where(keep, s_new, s)
            return s_new, s_new

        xs = (jnp.moveaxis(a, 1, 0), jnp.moveaxis(u, 1, 0),
              jnp.arange(length, dtype=jnp.int32))
        s_last, states = jax.lax.scan(step, s0, xs)
        y = jnp.moveaxis(states, 0, 1) * g  # [B, L, d_state]
        out = x + self.out_dropout(
            self.out_proj(Tensor(y, stop_gradient=True)))
        if cache is None:
            return out
        new_cache = cache._replace(
            state=s_last,
            index=jnp.asarray(idx, jnp.int32) + jnp.int32(length))
        return out, new_cache


class SSMLM(Layer):
    """Recurrent (SSM) language model with tied input/output embeddings.

    The ``TransformerLM`` of the O(1)-cache class: same
    ``forward(input_ids, cache=...)`` / ``gen_decode_cache`` surface,
    so ``DecodeSession``/``GenerationPool``/``ServingEngine`` serve it
    unchanged — but every layer's cache entry is of the ``"recurrent"``
    layout (a typed error names the mismatch for any other, and
    ``cache_layouts`` advertises the supported set the session checks at
    construction; a model whose layers keep different kinds of entry is
    ``models.HybridMambaLM``, ``jit.cache.layout_of``).
    No position embeddings: position is implicit in the recurrence, so
    ``max_len`` is bounded only by the caller's budget, not a table.
    """

    #: layouts gen_decode_cache can build (DecodeSession validates
    #: against this at construction; TransformerLM's positional
    #: attention conversely serves only "dense"/"paged")
    cache_layouts = ("recurrent",)
    causal = True

    def __init__(self, vocab_size: int = 30528, hidden_size: int = 768,
                 num_layers: int = 12, d_state: Optional[int] = None,
                 dropout: float = 0.0):
        super().__init__()
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.d_state = int(d_state) if d_state else 2 * int(hidden_size)
        self.word_embeddings = Embedding(vocab_size, hidden_size)
        self.embed_dropout = Dropout(dropout)
        self.blocks = LayerList([
            GatedSSMBlock(hidden_size, self.d_state, dropout=dropout)
            for _ in range(num_layers)])
        self.final_norm = LayerNorm(hidden_size)

    def gen_decode_cache(self, batch_size: int, max_length: int,
                         dtype="float32", per_slot: bool = False,
                         layout: str = "recurrent", block_size: int = 32,
                         num_blocks: Optional[int] = None):
        """Per-layer :data:`RecurrentDecodeCache` — constant
        ``[batch, d_state]`` fp32 state, O(1) per token.

        Only ``layout="recurrent"`` exists for this model class (there
        is no positional K/V to page or densify), and only fp32 state:
        the carry IS the exact decode state — quantizing it would
        change every later token, where an int8 K/V cache only
        perturbs values that are re-read under known scales.
        """
        if layout != "recurrent":
            raise InvalidArgumentError(
                "SSMLM keeps a constant-size recurrence carry, not "
                "positional K/V: cache_layout=%r does not exist for "
                "this model class — construct the session/pool with "
                "cache_layout='recurrent' (the 'dense'/'paged' layouts "
                "belong to attention models like TransformerLM)"
                % (layout,))
        if str(dtype) != "float32":
            raise InvalidArgumentError(
                "recurrent decode state supports only dtype='float32' "
                "(got %r): the carry is the EXACT serving state — "
                "quantizing it would change every subsequent token, "
                "not just re-read precision" % (dtype,))
        index = (jnp.zeros((batch_size,), jnp.int32) if per_slot
                 else jnp.asarray(0, jnp.int32))
        limit = jnp.asarray(int(max_length), jnp.int32)
        return [RecurrentDecodeCache(
            state=jnp.zeros((batch_size, self.d_state), jnp.float32),
            index=index, limit=limit) for _ in range(self.num_layers)]

    def forward(self, input_ids, attn_mask=None, token_type_ids=None,
                cache=None):
        """Logits ``[B, L, V]`` (+ successor cache when given).

        ``attn_mask``/``token_type_ids`` are accepted for surface
        parity with ``TransformerLM`` and ignored — causality is
        structural in a recurrence (state at t reads positions < t by
        construction), so there is no mask to apply.
        """
        h = self.embed_dropout(self.word_embeddings(input_ids))
        if cache is not None:
            new_cache = []
            for block, c in zip(self.blocks, cache):
                h, nc = block(h, cache=c)
                new_cache.append(nc)
            h = self.final_norm(h)
            logits = Tensor(
                jnp.matmul(h.value, self.word_embeddings.weight.value.T),
                stop_gradient=True)
            return logits, new_cache
        for block in self.blocks:
            h = block(h)
        h = self.final_norm(h)
        return Tensor(
            jnp.matmul(h.value, self.word_embeddings.weight.value.T),
            stop_gradient=True)

    def flops_per_token(self, seq_len: int) -> float:
        """Analytic fwd+bwd FLOPs/token (MFU accounting): 6 × matmul
        params — the recurrence itself is O(d_state) elementwise, a
        rounding error next to the projections."""
        per_layer = 3 * self.hidden_size * self.d_state \
            + self.d_state * self.hidden_size
        matmul_params = self.num_layers * per_layer \
            + self.vocab_size * self.hidden_size
        return 6.0 * matmul_params
