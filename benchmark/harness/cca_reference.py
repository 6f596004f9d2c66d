"""Plain reference for a decoder of compressed convolutional attention (CCA,
arXiv:2510.04476) and top-1 routed experts under a router that carries a
state through the depth of the model (ZAYA1-8B, ``model_type: zaya``; the
ZAYA1 report arXiv:2511.17127): the forward in straightforward ``jax.numpy``,
float32, matmuls at ``highest``.  No cache, no state kept between calls, no
batching, no kernel, and nothing imported from the program: the convolutions
are shifted sums over the whole sequence, attention the plain causal softmax
(a block of queries at a time; every block sees the whole key array), every
expert runs on every row under a gate that is 0 where it was not chosen.

For a layer's input ``h`` [T, H] and the router state ``r_prev`` [T, R] of
the layer before, ``n`` query heads on ``n_kv`` K/V heads of ``d``, ``G = n /
n_kv``::

    a   = rmsnorm(h)
    u   = [a W_q , a W_k]                                   [T, (n + n_kv) d]
    c0[t, c]    = sum_j w0[c, j] u[t - (k0-1) + j, c] + b0[c]      depthwise, causal
    c1[t, g, o] = sum_j sum_i w1[g, o, i, j] c0[t - (k1-1) + j, g, i] + b1[g, o]
                  grouped over the n + n_kv heads; zeros before t = 0 in both
    q~_i = u's head i (i < n), k~_j = u's head n + j
    m_i  = (q~_i + k~_{i // G}) / 2;  mbar_j = mean over group j's heads of m_i
    q_i  = c1's head i + m_i;  k_j = c1's head n + j + mbar_j
    q_i  = sqrt(d) q_i / |q_i|;  k_j = sqrt(d) k_j / |k_j| * temp_j
    q, k = rotary over the first ``rotary`` channels of a head, halves paired
    v[t] = [a[t] W_v1 , a[t-1] W_v2]   as n_kv heads of d; a[-1] = 0
    o_i[t] = sum_{s<=t} softmax_s(q_i[t] . k_{i//G}[s] / sqrt(d)) v_{i//G}[s]
    h   = h + concat_i(o_i) W_o

    m   = rmsnorm(h)
    r   = m W_dn + b_dn  (+ gamma * r_prev: every layer but the first)
    z   = rmsnorm(r)
    s   = W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2)             gelu by erf
    p   = softmax(s);  e = argmax p
    h   = h + p_e * W_down_e (silu(m W_gate_e) * (m W_up_e))
    end: logits = rmsnorm(h) E^T       (E the embedding: tied)

``mode``: ``float32`` is the reference proper; ``fp8`` is the control for
the stated bfloat16 (matmul operands through float8_e4m3fn, as
``reference._mm``); ``no_mix`` is the other control: float32, ``q = q~``, ``k
= k~`` and both value halves from the current token, CCA with its mixing
along the sequence left out, so that a program that skips the mechanism
fails the comparison.

Departures from the release and what the config does not settle: the
configuration's ``departures`` and ``assumed``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms
from .reference import _mm

QUERY_BLOCK = 256


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the mixing's control keeps float32
    matmuls)."""
    return "fp8" if mode == "fp8" else "float32"


def causal_taps(x, taps: int):
    """``x`` [T, ...] shifted: element ``j`` is ``x[t - (taps - 1) + j]``,
    zeros before position 0."""
    t = x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0),) + ((0, 0),) * (x.ndim - 1))
    return [padded[j:j + t] for j in range(taps)]


def rotary(x, pos, width: int, theta: float):
    """``x`` [T, heads, d]: the first ``width`` channels turned at ``pos``
    [T], channel ``i`` paired with ``i + width / 2``."""
    half = width // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], axis=-1)


def attention(a, p, pos, sizes, mode):
    mm = head_mode(mode)
    t = a.shape[0]
    n, nkv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    g = n // nkv
    u = jnp.concatenate([_mm(a, p["w_q"], mm), _mm(a, p["w_k"], mm)],
                        axis=-1).reshape(t, n + nkv, d)
    q, k = u[:, :n], u[:, n:]
    v_now, v_next = _mm(a, p["w_v1"], mm), _mm(a, p["w_v2"], mm)
    v_before = v_next
    if mode != "no_mix":
        c0 = p["b0"] + sum(
            p["w0"][:, j] * x for j, x in enumerate(
                causal_taps(u.reshape(t, -1), sizes["k0"])))
        c1 = p["b1"] + sum(
            _mm(x.transpose(1, 0, 2), p["w1"][..., j].transpose(0, 2, 1),
                mm).transpose(1, 0, 2)
            for j, x in enumerate(causal_taps(c0.reshape(t, n + nkv, d),
                                              sizes["k1"])))
        m = (q + jnp.repeat(k, g, axis=1)) / 2
        q = c1[:, :n] + m
        k = c1[:, n:] + jnp.mean(m.reshape(t, nkv, g, d), axis=2)
        v_before = causal_taps(v_next, 2)[0]

    def unit(x):
        return math.sqrt(d) * x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = rotary(unit(q), pos, sizes["rotary"], sizes["rope_theta"])
    k = rotary(unit(k) * p["temp"][:, None], pos, sizes["rotary"],
               sizes["rope_theta"])
    v = jnp.concatenate([v_now, v_before], axis=-1).reshape(t, nkv, d)
    kt = jnp.repeat(k, g, axis=1).transpose(1, 2, 0)          # [n, d, T]
    vt = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)          # [n, T, d]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk).transpose(1, 0, 2)
        s = _mm(qb, kt, mm) / math.sqrt(d)                    # [n, blk, T]
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vt, mm)        # [n, blk, d]

    o = jax.lax.map(block, jnp.arange(0, t, blk))             # [T/blk, n, blk, d]
    return _mm(o.transpose(0, 2, 1, 3).reshape(t, n * d), p["w_o"], mm)


def router(m, r_prev, p, sizes, mm):
    """``(p [T, E], r [T, R])``."""
    r = _mm(m, p["r_down"], mm) + p["r_down_b"]
    if r_prev is not None:
        r = r + p["r_gamma"] * r_prev
    z = _rms(r, p["r_norm"], sizes["norm_eps"])
    x = jax.nn.gelu(_mm(z, p["r_w1"], mm) + p["r_b1"], approximate=False)
    x = jax.nn.gelu(_mm(x, p["r_w2"], mm) + p["r_b2"], approximate=False)
    return jax.nn.softmax(_mm(x, p["r_w3"], mm), axis=-1), r


def experts(m, probs, p, mm):
    """The chosen expert's output under its probability, every expert on
    every row."""
    chosen = jnp.argmax(probs, axis=-1)
    out = jnp.zeros_like(m)
    for e in range(probs.shape[-1]):
        y = _mm(jax.nn.silu(_mm(m, p["e_gate"][e], mm))
                * _mm(m, p["e_up"][e], mm), p["e_down"][e], mm)
        out = out + jnp.where(chosen == e, probs[:, e], 0.0)[:, None] * y
    return out


def layer_forward(h, r_prev, p, pos, sizes, mode: str):
    """One layer on ``h`` [T, H] and the router state of the layer before
    (None: the first layer); ``(h, r)``."""
    mm, eps = head_mode(mode), sizes["norm_eps"]
    h = h + attention(_rms(h, p["in_norm"], eps), p, pos, sizes, mode)
    m = _rms(h, p["post_norm"], eps)
    probs, r = router(m, r_prev, p, sizes, mm)
    return h + experts(m, probs, p, mm), r


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, r_prev, p, pos, sizes, mode):
    return layer_forward(h, r_prev, p, pos, dict(sizes), mode)


def head_logits(h, final_norm, head, eps, mode):
    return _mm(_rms(h, final_norm, eps), head, mode)


def hidden_states(embed, layers, ids, sizes, mode: str = "float32"):
    """The last layer's output [T, H] of one sequence from position 0;
    ``layers`` is an iterable of the layers' weights, read one at a time."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    h, r = embed[ids].astype(jnp.float32), None
    for p in layers:
        h, r = _layer_jit(h, r, p, pos, sizes_key(sizes), mode)
    return h


def forward_logits(weights, ids, sizes, mode: str = "float32"):
    """Logits [T, V] of one sequence from position 0 (small sizes)."""
    h = hidden_states(weights["embed"], weights["layers"], ids, sizes, mode)
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], head_mode(mode))
