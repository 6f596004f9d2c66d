"""Plain reference for a decoder whose stack runs several times on shared
weights (ByteDance Ouro, ``model_type: ouro``; the LoopLM family,
arXiv:2510.25741): the forward in straightforward ``jax.numpy``, float32,
matmuls at ``highest``.  No cache, no batching, no kernel, no loop construct
of the program's (a Python ``for`` over passes and layers), and nothing
imported from the program.

On ``h`` [T, H], the SAME layers' weights in every pass::

    h_0 = embedding(ids)
    for r in 0..R-1:                                R = total_ut_steps
        for l in 0..layers-1:
            a = rmsnorm_in1[l](h);  q,k,v = a Wq, a Wk, a Wv      no bias, no QK-norm
            q,k = rotary(q,k; theta, halves rotated, position i)  the same positions in every pass
            o = softmax(q K_(r,l)^T / sqrt(D) + causal) V_(r,l)   K/V of pass r, layer l
            h = h + rmsnorm_in2[l](o Wo)                          a norm AFTER the sub-layer too
            m = rmsnorm_post1[l](h)
            h = h + rmsnorm_post2[l](Wdown (silu(Wgate m) * (Wup m)))
        h = final_norm(h)                           after EVERY pass
        s_r = h;   lambda_r = sigmoid(h w_gate + b_gate)
    p_r = lambda_r * prod_{j<r}(1 - lambda_j) for r < R-1;  p_{R-1} = prod_{j<R-1}(1 - lambda_j)
    exit = first r with sum_{j<=r} p_j >= early_exit_threshold, else R-1
    logits = s_exit Whead                           untied head

``mode``: ``float32`` is the reference proper.  Three controls, what a
faulty program would compute: ``fp8`` (matmul operands through
float8_e4m3fn with a per-tensor scale, as ``reference._mm``: the precision
below the stated bfloat16); ``one_pass_fewer`` (R - 1 passes);
``shared_planes`` (every pass attends the LAST pass's K/V of its layer: one
K/V plane a layer where the model keeps R, the tempting saving of three
quarters of the cache; the last pass's K/V are those of a sound forward).

What is not a key of ``config.json`` (each also under the configuration's
``assumed``): the four norms a layer and the final norm between passes, the
gate and the exit distribution, one K/V a (pass, layer), no bias, the
halves-rotated rotary: from the release's modelling file and the paper.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms, _rotary
from .reference import _mm

CONTROLS = ("fp8", "one_pass_fewer", "shared_planes")


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the controls of the structure keep
    float32 matmuls)."""
    return mode if mode in ("float32", "fp8") else "float32"


def passes_of(sizes: dict, mode: str) -> int:
    return sizes["passes"] - (mode == "one_pass_fewer")


def layer_forward(h, p, pos, sizes, mode: str, kv=None):
    """One layer on ``h`` [T, H] float32: ``(h, (k, v))``, the layer's own
    rotated keys and values ``[T, n_kv, D]``.  ``kv`` given: attention reads
    those instead (the shared-planes control)."""
    mm, eps, t = head_mode(mode), sizes["norm_eps"], h.shape[0]
    n, nkv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    a = _rms(h, p["in_norm"], eps)
    q = _rotary(_mm(a, p["wq"], mm).reshape(t, n, d), pos,
                sizes["rope_theta"])
    k = _rotary(_mm(a, p["wk"], mm).reshape(t, nkv, d), pos,
                sizes["rope_theta"])
    v = _mm(a, p["wv"], mm).reshape(t, nkv, d)
    own = (k, v)
    if kv is not None:
        k, v = kv
    k, v = (jnp.repeat(x, n // nkv, axis=1) for x in (k, v))
    s = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), mm) / math.sqrt(d)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = _mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2), mm)
    o = _mm(o.transpose(1, 0, 2).reshape(t, n * d), p["wo"], mm)
    h = h + _rms(o, p["attn_out_norm"], eps)
    m = _rms(h, p["post_norm"], eps)
    f = _mm(jax.nn.silu(_mm(m, p["w_gate"], mm)) * _mm(m, p["w_up"], mm),
            p["w_down"], mm)
    return h + _rms(f, p["mlp_out_norm"], eps), own


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, p, pos, kv, sizes, mode):
    return layer_forward(h, p, pos, dict(sizes), mode, kv)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def pass_end(h, final_norm, exit_w, exit_b, eps, mode):
    """The end of a pass: ``(final_norm(h), lambda [T])``."""
    h = _rms(h, final_norm, eps)
    return h, jax.nn.sigmoid(_mm(h, exit_w, head_mode(mode))[:, 0]
                             + exit_b[0])


@jax.jit
def exit_select(states, gates, threshold):
    """``s_exit`` [T, H] from ``states`` [R, T, H] and ``gates`` [R, T]."""
    r = gates.shape[0]
    staying = jnp.concatenate(
        [jnp.ones_like(gates[:1]), jnp.cumprod(1.0 - gates[:-1], axis=0)])
    p = jnp.concatenate([gates[:-1] * staying[:-1], staying[-1:]])
    reached = jnp.cumsum(p, axis=0) >= threshold
    at = jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0),
                   r - 1)
    return jnp.take_along_axis(states, at[None, :, None], axis=0)[0]


def exit_hidden(streams: list, top: dict, layer_of, layers: int, pos,
                sizes: dict, mode: str) -> list:
    """``s_exit`` [T, H] of every stream (``h_0`` [T, H] float32), the
    passes outermost: ``layer_of(l)`` makes layer ``l``'s float32 weights,
    once a pass, for every stream."""
    key, eps = sizes_key(sizes), sizes["norm_eps"]
    shared = None
    if mode == "shared_planes":
        # a sound forward first: the last pass's K/V of every layer
        shared = [[] for _ in streams]
        hs = list(streams)
        for r in range(sizes["passes"]):
            for l in range(layers):
                p = layer_of(l)
                for i, h in enumerate(hs):
                    hs[i], kv = _layer_jit(h, p, pos, None, key, "float32")
                    if r == sizes["passes"] - 1:
                        shared[i].append(kv)
            hs = [pass_end(h, top["final_norm"], top["exit_w"],
                           top["exit_b"], eps, "float32")[0] for h in hs]
    hs, states, gates = list(streams), [[] for _ in streams], \
        [[] for _ in streams]
    for r in range(passes_of(sizes, mode)):
        for l in range(layers):
            p = layer_of(l)
            hs = [_layer_jit(h, p, pos, shared and shared[i][l], key,
                             mode)[0] for i, h in enumerate(hs)]
            jax.block_until_ready(hs)
        for i, h in enumerate(hs):
            hs[i], g = pass_end(h, top["final_norm"], top["exit_w"],
                                top["exit_b"], eps, mode)
            states[i].append(hs[i])
            gates[i].append(g)
    return [exit_select(jnp.stack(s), jnp.stack(g), sizes["threshold"])
            for s, g in zip(states, gates)]


def head_logits(h, head, mode: str):
    return _mm(h, head, head_mode(mode))


def forward_logits(weights, ids, sizes, mode: str = "float32"):
    """Logits [T, V] of one sequence from position 0 (small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    h = weights["embed"][ids].astype(jnp.float32)
    (h,) = exit_hidden([h], weights, lambda l: weights["layers"][l],
                       len(weights["layers"]), jnp.arange(ids.shape[0]),
                       sizes, mode)
    return head_logits(h, weights["head"], mode)
