"""Sparse mixture-of-experts feed-forward for serving: every token goes to
its ``top_k`` experts and none is ever dropped.

The training layer (``distributed/meta_parallel/moe_layer.py``) gives each
expert a fixed capacity and drops what overflows, which a trainer can live
with and a server cannot: a dropped token is a wrong answer.  Here the
(token, expert) pairs are sorted by expert and the experts' stacked weights
multiply them as grouped matmuls (``jax.lax.ragged_dot``: on a TPU one
kernel each, whose work follows the routed rows, whatever the imbalance).
A step of few rows touches every expert anyway and runs every expert on
every row instead (``sparse_experts`` says where the line is and why).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def route_top_k(logits, top_k: int):
    """Softmax over ALL experts in float32, the ``top_k`` largest, their
    probabilities renormalised to sum to 1 (``norm_topk_prob``).  Returns
    ``(gates [T, k] float32, experts [T, k] int32)``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), experts.astype(
        jnp.int32)


def _grouped(xt, gates, key, held: int, top_k: int, w_gate, w_up, w_down):
    """The pairs sorted by expert, three grouped matmuls over the stacked
    weights, un-sorted and summed with their gates in float32."""
    rows, width = xt.shape
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    xs = xt[order // top_k]
    act = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) \
        * jax.lax.ragged_dot(xs, w_up, sizes)
    ys = jax.lax.ragged_dot(act.astype(xt.dtype), w_down, sizes)
    with jax.named_scope("combine"):
        per_pair = ys[jnp.argsort(order)].reshape(rows, top_k, width)
        # a pair of an expert held elsewhere lies past the last group,
        # where the grouped matmul computed nothing: leave it out
        mine = (key < held).reshape(rows, top_k, 1)
        return jnp.sum(jnp.where(mine, per_pair.astype(jnp.float32), 0.0)
                       * gates[..., None], axis=1)


def _every_expert(xt, gates, key, held: int, top_k: int, w_gate, w_up,
                  w_down):
    """Every held expert on every row, an unchosen one under a gate of
    0: one batched matmul in, and one matmul out that sums over experts
    and their channels at once.  Few rows touch every expert anyway, and
    then the weights' read is the cost and this reads them once."""
    rows = xt.shape[0]
    gate_of = jnp.sum(
        jnp.where(key.reshape(rows, top_k, 1) == jnp.arange(held),
                  gates[..., None], 0.0), axis=1)               # [T, n]
    act = jax.nn.silu(jnp.einsum("th,ehf->tef", xt, w_gate)) \
        * jnp.einsum("th,ehf->tef", xt, w_up)
    with jax.named_scope("combine"):
        act = (act.astype(jnp.float32) * gate_of[..., None]).astype(xt.dtype)
        return jnp.einsum("tef,efh->th", act, w_down,
                          preferred_element_type=jnp.float32)


def sparse_experts(x, router_w, w_gate, w_up, w_down, top_k: int,
                   first_expert: int = 0):
    """``x`` ``[..., H]`` through a routed gated-SiLU feed-forward.

    ``router_w`` ``[H, E]`` scores every one of the ``E`` experts;
    ``w_gate``/``w_up`` ``[n, H, F]`` and ``w_down`` ``[n, F, H]`` are the
    weights of the ``n`` experts this call HOLDS, experts ``first_expert
    .. first_expert + n - 1`` (all of them when ``n == E``).  A pair routed
    to an expert that is not held adds nothing here: its holder adds it,
    and the caller sums the holders.  Routing always runs over all ``E``,
    so every holder agrees on the gates.

    ``out[t] = sum over t's top_k experts e of gate[t, e] *
    w_down[e] (silu(x[t] w_gate[e]) * (x[t] w_up[e]))``.

    Two routes, chosen from the shapes alone.  Up to ``4 * n`` rows every
    held expert runs on every row under a gate that is 0 where it was not
    chosen; above, the pairs are sorted by expert and go through grouped
    matmuls whose work follows the routed rows.  Read on a TPU v5e at 128
    experts of 2048 x 768, 8 a token, one layer, every expert on every
    row against grouped, in ms (PR 28): 128 rows 1.65 / 4.48 (the
    weights' read alone is 1.47, and the grouped kernel's groups of 8 rows
    leave it far from that); 512 rows 3.32 / 4.81; 768 rows 4.90 / 5.05;
    1,024 rows 7.05 / 5.27; 2,048 rows 14.04 / 6.09.  Neither drops a
    token.
    """
    lead, width = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, width)
    held = w_gate.shape[0]
    with jax.named_scope("router"):
        # scores accumulated and kept in float32: a score rounded to
        # the activations' type would swap experts at the cut
        gates, experts = route_top_k(
            jnp.matmul(xt, router_w, preferred_element_type=jnp.float32),
            top_k)
        # pairs in token-major order under the held experts' own
        # numbering; an expert held elsewhere gets the key ``held``
        local = experts.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
    with jax.named_scope("experts"):
        route = _every_expert if xt.shape[0] <= 4 * held else _grouped
        out = route(xt, gates, key, held, top_k, w_gate, w_up, w_down)
    return out.astype(x.dtype).reshape(*lead, width)
