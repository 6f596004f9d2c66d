"""Sparse mixture-of-experts feed-forward for serving: every token goes to
its ``top_k`` experts and none is ever dropped.

The training layer (``distributed/meta_parallel/moe_layer.py``) gives each
expert a fixed capacity and drops what overflows, which a trainer can live
with and a server cannot: a dropped token is a wrong answer.  Here the
(token, expert) pairs are sorted by expert and the experts' stacked weights
multiply them as grouped matmuls (``jax.lax.ragged_dot``: on a TPU one
kernel each, whose work follows the routed rows, whatever the imbalance).
A step of few rows runs every held expert on every row instead, and where
its rows will leave a good part of the held experts untouched, only the
experts some row chose (``sparse_experts`` says where the lines are and
why).

The router's SCORES are the caller's (``nn.SparseExperts`` computes them:
one matrix, or a layer with a state of its own); its rule is data
(``route_top_k``): softmax over all experts then the ``top_k`` largest (the
Qwen3 family), or sigmoid scores, a limit to the best ``topk_group`` of
``n_group`` groups of consecutive experts, and a scale on the gates (the
DeepSeek-V3 family); the chosen gates renormalised or left as they stand.  A
call may HOLD a share of the experts (``first_expert``, the stacked weights'
leading size): it adds its share's part of the sum, and the holders' parts
add up to the layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...profiler import DEVICE_PEAKS


# Multiply-adds a matrix up to which every held expert runs on every row:
# 512 rows x 128 experts of 2048 x 768, where the two routes were read to
# cross on a TPU v5e (``sparse_experts``).
_EVERY_EXPERT_MACS = 512 * 128 * 2048 * 768

# What a turn of the touched route's loop costs over an expert's share of
# ``_every_expert``: its three products start and drain one after the other
# where that form streams every expert through two batched products and
# one.  Under the multiply-add line the touched route is taken where the
# time an expert's read is EXPECTED to save, the chance that no row chose
# it times its bytes over the chip's stream, exceeds this.  Read on a TPU
# v5e with ``tools/expert_route_bench.py``: 6.2-7.3 us at ax-k1's geometry (5.0
# and 9.9 at zaya's and sdar's, where nothing is expected to be saved;
# ``sparse_experts`` has the table).
_SKIP_COST_S = 7e-6
# both constants were read on a TPU v5e: the stream is that chip's
_HBM_BYTES_PER_S = DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_sec"]


def touched_share(rows: int, num_experts: int, top_k: int) -> float:
    """The expected share of the experts that ``rows`` rows touch, each
    choosing ``top_k`` of ``num_experts`` evenly: ``1 - (1 - k / E) ^
    rows``.  A router that prefers some experts touches fewer."""
    return 1.0 - (1.0 - top_k / num_experts) ** rows


def expert_route(rows: int, held: int, num_experts: int, top_k: int,
                 width: int, expert_size: int, itemsize: int) -> str:
    """The route ``sparse_experts`` takes at these shapes: ``"grouped"``,
    ``"every"`` or ``"touched"``.  A function of the shapes alone: the
    pool reads it for its spans without tracing anything."""
    if rows * held * width * expert_size > _EVERY_EXPERT_MACS:
        return "grouped"
    saved = (1.0 - touched_share(rows, num_experts, top_k)) \
        * 3 * width * expert_size * itemsize / _HBM_BYTES_PER_S
    return "touched" if saved > _SKIP_COST_S else "every"


def route_top_k(logits, top_k: int, scoring: str = "softmax",
                n_group: int = 1, topk_group: int = 1,
                scale: float = 1.0, renormalise: bool = True):
    """The router's choice, ``(gates [T, k] float32, experts [T, k]
    int32)``, from ``logits`` ``[T, E]`` over ALL experts, in float32.

    ``scoring="softmax"``: softmax over all experts, the ``top_k`` largest,
    their probabilities renormalised to sum to 1 (``norm_topk_prob``).
    ``renormalise=False`` leaves them as they stand: with one expert a
    token the gate is then the chosen expert's probability, where the
    renormalised gate is 1.0 whatever the router said.

    ``scoring="sigmoid"``: a score ``s = sigmoid(logit)`` an expert on its
    own.  The ``E`` experts are ``n_group`` groups of ``E / n_group``
    consecutive ones; a group's score is the sum of its 2 largest ``s``;
    only the ``topk_group`` best groups stay eligible, and the ``top_k``
    largest ``s`` among them are chosen (``n_group`` 1: plain top-k).  The
    gates are ``s_e / (sum of the chosen s + 1e-20) * scale``
    (``norm_topk_prob``, ``routed_scaling_factor``; ``renormalise=False``:
    ``s_e * scale``).  This is the DeepSeek-V3 rule without its bias
    term."""
    if scoring == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top, experts = jax.lax.top_k(probs, top_k)
        gates = top / jnp.sum(top, axis=-1, keepdims=True) \
            if renormalise else top
        return (gates if scale == 1.0 else gates * scale), \
            experts.astype(jnp.int32)
    if scoring != "sigmoid":
        raise ValueError("scoring must be 'softmax' or 'sigmoid', got %r"
                         % (scoring,))
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    rows, n = s.shape
    choose = s
    if n_group > 1:
        by_group = s.reshape(rows, n_group, n // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)      # [T, g]
        eligible = jnp.any(kept[:, :, None] == jnp.arange(n_group),
                           axis=1)                            # [T, G]
        # a sigmoid is above 0: an expert outside the kept groups is
        # never among the largest
        choose = jnp.where(eligible[:, :, None], by_group, -1.0) \
            .reshape(rows, n)
    top, experts = jax.lax.top_k(choose, top_k)
    if renormalise:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * scale, experts.astype(jnp.int32)


# What gates an expert's up-projection: the name a layer is built with
# (``nn.SparseExperts(activation=)``), so the three routes take it as data.
EXPERT_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _grouped(xt, gates, key, held: int, top_k: int, w_gate, w_up, w_down,
             act=jax.nn.silu):
    """The pairs sorted by expert, three grouped matmuls over the stacked
    weights, un-sorted and summed with their gates in float32."""
    rows, width = xt.shape
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    xs = xt[order // top_k]
    hid = act(jax.lax.ragged_dot(xs, w_gate, sizes)) \
        * jax.lax.ragged_dot(xs, w_up, sizes)
    ys = jax.lax.ragged_dot(hid.astype(xt.dtype), w_down, sizes)
    with jax.named_scope("combine"):
        per_pair = ys[jnp.argsort(order)].reshape(rows, top_k, width)
        # a pair of an expert held elsewhere lies past the last group,
        # where the grouped matmul computed nothing: leave it out
        mine = (key < held).reshape(rows, top_k, 1)
        return jnp.sum(jnp.where(mine, per_pair.astype(jnp.float32), 0.0)
                       * gates[..., None], axis=1)


def _gate_of(gates, key, held: int):
    """``[T, n]`` float32: a row's gate on each held expert, 0 where the
    row did not choose it."""
    rows, top_k = gates.shape
    return jnp.sum(
        jnp.where(key.reshape(rows, top_k, 1) == jnp.arange(held),
                  gates[..., None], 0.0), axis=1)


def _every_expert(xt, gates, key, held: int, top_k: int, w_gate, w_up,
                  w_down, act=jax.nn.silu):
    """Every held expert on every row, an unchosen one under a gate of
    0: one batched matmul in, and one matmul out that sums over experts
    and their channels at once.  Many rows touch every expert anyway, and
    then the weights' read is the cost and this reads them once."""
    gate_of = _gate_of(gates, key, held)                        # [T, n]
    hid = act(jnp.einsum("th,ehf->tef", xt, w_gate)) \
        * jnp.einsum("th,ehf->tef", xt, w_up)
    with jax.named_scope("combine"):
        hid = (hid.astype(jnp.float32) * gate_of[..., None]).astype(xt.dtype)
        return jnp.einsum("tef,efh->th", hid, w_down,
                          preferred_element_type=jnp.float32)


def _touched(xt, gates, key, held: int, top_k: int, w_gate, w_up, w_down,
             act=jax.nn.silu):
    """Only the held experts that some row chose, each on every row under
    its column of the gates: the touched experts' numbers stand first in
    ``order`` and a loop on the device runs as many turns as there are.
    An untouched expert's weights are never read.  A turn takes its
    expert's three matrices out of the stacked arrays INSIDE the body, so
    each slice is an operand of its product and no matrix is copied; the
    sum over experts is kept in float32, as ``_every_expert``'s is.  A
    turn's three products start and drain one after the other where
    ``_every_expert`` streams all experts through two, so where the rows
    chose EVERY held expert that form runs instead: the route costs a
    branch where it can skip nothing.  The trip count is data, so nothing
    differentiates through this route (jax raises at a reverse-mode
    trace; the layer serves and stops the gradient)."""
    sizes = jnp.bincount(key, length=held + 1)[:held]
    touched = jnp.sum(sizes > 0, dtype=jnp.int32)

    def the_touched():
        gate_of = _gate_of(gates, key, held)                    # [T, n]
        order = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)

        def one_expert(turn, out):
            e = order[turn]
            w_g, w_u, w_d, gate = (
                jax.lax.dynamic_index_in_dim(a, e, axis, keepdims=False)
                for a, axis in ((w_gate, 0), (w_up, 0), (w_down, 0),
                                (gate_of, 1)))
            hid = act(jnp.matmul(xt, w_g)) * jnp.matmul(xt, w_u)
            with jax.named_scope("combine"):
                hid = (hid.astype(jnp.float32) * gate[:, None]) \
                    .astype(xt.dtype)
                return out + jnp.matmul(hid, w_d,
                                        preferred_element_type=jnp.float32)

        return jax.lax.fori_loop(0, touched, one_expert,
                                 jnp.zeros(xt.shape, jnp.float32))

    return jax.lax.cond(
        touched == held,
        lambda: _every_expert(xt, gates, key, held, top_k, w_gate, w_up,
                              w_down, act),
        the_touched)


_ROUTES = {"grouped": _grouped, "every": _every_expert, "touched": _touched}


def sparse_experts(x, scores, w_gate, w_up, w_down, top_k: int,
                   first_expert: int = 0, scoring: str = "softmax",
                   n_group: int = 1, topk_group: int = 1,
                   routed_scale: float = 1.0, renormalise: bool = True,
                   activation: str = "silu"):
    """``x`` ``[..., H]`` through a routed gated feed-forward whose gate is
    ``activation`` (``"silu"``: the Qwen3 and DeepSeek families'; ``"relu"``:
    SmallThinker's sparse ReGLU, computed in full, zeros and all).

    ``scores`` ``[..., E]`` float32 are the router's logits of every one of
    the ``E`` experts, a row for each row of ``x``, computed by the caller
    (accumulated and kept in float32: a score rounded to the activations'
    type would swap experts at the cut);
    ``w_gate``/``w_up`` ``[n, H, F]`` and ``w_down`` ``[n, F, H]`` are the
    weights of the ``n`` experts this call HOLDS, experts ``first_expert
    .. first_expert + n - 1`` (all of them when ``n == E``).  A pair routed
    to an expert that is not held adds nothing here: its holder adds it,
    and the caller sums the holders.  Routing always runs over all ``E``,
    so every holder agrees on the gates; ``scoring``, ``n_group``,
    ``topk_group``, ``routed_scale`` and ``renormalise`` are the router's
    rule (``route_top_k``).

    ``out[t] = sum over t's top_k experts e of gate[t, e] *
    w_down[e] (activation(x[t] w_gate[e]) * (x[t] w_up[e]))``.

    Three routes, chosen from the shapes alone (``expert_route``).  While
    the held experts' matmuls over every row stay under
    ``_EVERY_EXPERT_MACS`` multiply-adds a matrix (``rows x n x H x F``),
    every held expert runs on every row under a gate that is 0 where it
    was not chosen; above, the pairs are sorted by expert and go through
    grouped matmuls whose work follows the routed rows.  Under that line,
    where the rows are so few that a good part of the held experts will go
    untouched, only the experts some row chose run, in a loop on the
    device, and an untouched expert's weights are never read: taken where
    ``(1 - k / E) ^ rows x an expert's bytes / 819 GB/s``, the read an
    expert is expected to save, exceeds ``_SKIP_COST_S``, what a turn of
    that loop costs over the expert's share of the batched form.

    Read on a TPU v5e, one layer, every expert on every row against
    grouped, in ms.  All 128 experts of 2048 x 768 held, 8 a token
    (PR 28): 128 rows 1.65 / 4.48 (the weights' read alone is 1.47, and the
    grouped kernel's groups of 8 rows leave it far from that); 512 rows
    3.32 / 4.81; 768 rows 4.90 / 5.05; 1,024 rows 7.05 / 5.27; 2,048 rows
    14.04 / 6.09: the line at 512 rows.  A share of 12 of 192 experts of
    7168 x 2048 held, 8 a token (PR 40): 32 rows 1.58 / 2.35 (the weights'
    read alone is 1.29); 64 rows 1.60 / 3.56; 128 rows 1.58 / 3.62; 2,048
    rows 12.82 / 6.98; 8,192 rows - / 18.58: an expert there is nine times
    the work a row, so the same count of multiply-adds puts the line at 585
    rows (a rule in rows a held expert, 4 x 12 = 48, sent 64 and 128 rows
    to the grouped route at twice the time).

    The touched route against every expert on every row, the touched
    count forced (PR 45, ``tools/expert_route_bench.py``, twelve layers
    chained in one program, ms a layer).  12 of 192 experts of 7168 x 2048
    held, 32 rows: 1 touched 0.139 / 1.406, 3 0.388, 6 0.762, 9 (what 32
    rows are expected to touch: 8.9) **1.135 / 1.406**, 12 1.439 (+2.3 %).
    As a bare loop 12 touched took 1.486 (+5.7 %: a turn is 123.4 us where
    an expert's share of the batched form is 117.2 and its bytes alone
    107.5), so a step whose rows chose all twelve takes the batched form
    behind a branch, which costs every call 10-18 us (9 touched as a bare
    loop: 1.116).  The grouped route at those counts: 0.214, 0.568, 1.098,
    1.628, 2.157.  16 of 16 of 2048 x 2048, 64 rows, 1 a token: a turn
    costs 5.0 us over an expert's share and the rows leave ``(15/16) ^
    64`` = 1.6 % of an expert's 30.7 us unread: every expert stays.  128 of
    128 of 2048 x 768, 128 rows: 9.9 us a turn against nothing saved.  None
    of the three drops a token.
    """
    lead, width = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, width)
    held = w_gate.shape[0]
    with jax.named_scope("router"):
        # ``renormalise`` goes by name and only where it is not the
        # default: the accepted benchmark's tests stand a six-argument
        # rule in for ``route_top_k``
        gates, experts = route_top_k(
            scores.reshape(-1, scores.shape[-1]), top_k, scoring, n_group,
            topk_group, routed_scale,
            **({} if renormalise else {"renormalise": False}))
        # pairs in token-major order under the held experts' own
        # numbering; an expert held elsewhere gets the key ``held``
        local = experts.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
    with jax.named_scope("experts"):
        route = _ROUTES[expert_route(
            xt.shape[0], held, scores.shape[-1], top_k, width,
            w_gate.shape[2], w_gate.dtype.itemsize)]
        out = route(xt, gates, key, held, top_k, w_gate, w_up, w_down,
                    act=EXPERT_ACTIVATIONS[activation])
    return out.astype(x.dtype).reshape(*lead, width)
