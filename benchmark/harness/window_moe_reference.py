"""Plain reference for a sparse-expert decoder whose layers mix window and
global attention and whose router reads the layer's input (PowerInfer
SmallThinker, ``model_type: smallthinker``): the forward in straightforward
``jax.numpy``, float32, matmuls at ``highest``.  No cache, no ring, no
kernel, no batching; the band is a MASK over a full row of scores, taken in
blocks of queries so that 13 k positions fit; the experts are a loop with
masks (an expert a token did not choose enters under a gate of exactly 0, so
a token's sum is its chosen experts' alone); nothing is imported from the
program.

One layer ``l`` on ``h`` [T, H]::

    r = h Wr                        the router reads the layer's INPUT
    a = rmsnorm(h);  q, k, v = a Wq, a Wk, a Wv         no bias, no QK-norm
    rope_layout[l] = 1: q, k turned by rotary positions (halves rotated)
    o_i = softmax_j(q_i k_j / sqrt(D)) v_j   over j <= i, and where
          sliding_window_layout[l] = 1 over i - window < j <= i
    h = h + concat(o) Wo;   m = rmsnorm(h)
    E = the top_k largest of r;  g = softmax(r) renormalised over E
    h = h + sum over e in E of g_e * Wdown_e (relu(Wgate_e m) * (Wup_e m))

then a final RMSNorm and a head of its own.

``mode``: ``float32`` is the reference proper.  Four controls, what a
faulty program would compute: ``fp8`` (matmul operands through
float8_e4m3fn with a per-tensor scale, as ``reference._mm``: the precision
below the stated bfloat16); ``full_context`` (the window layers attend
their whole context: the band forgotten); ``router_post_norm`` (the router
reads ``rmsnorm`` of the post-attention stream, where every other expert
model of this benchmark reads it); ``silu_experts`` (``silu`` for
``relu``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms, _rotary
from .reference import _mm

CONTROLS = ("fp8", "full_context", "router_post_norm", "silu_experts")
QUERY_BLOCK = 256


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the controls of the structure keep
    float32 matmuls)."""
    return mode if mode in ("float32", "fp8") else "float32"


def attention(q, k, v, window, mm: str):
    """``q`` [T, n, D], ``k``, ``v`` [T, n, D] (the K/V heads repeated):
    the causal softmax, banded where ``window`` is set, a block of queries
    at a time: ``[n, block, T]`` scores and no more."""
    t, n, d = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kt = k.transpose(1, 2, 0)                                   # [n, D, T]
    vt = v.transpose(1, 0, 2)                                   # [n, T, D]
    j = jnp.arange(t)[None, None, :]

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        i = (start + jnp.arange(block))[None, :, None]
        s = _mm(qb.transpose(1, 0, 2), kt, mm) / math.sqrt(d)
        allow = j <= i
        if window is not None:
            allow = allow & (i - j < window)
        w = jax.nn.softmax(jnp.where(allow, s, -jnp.inf), axis=-1)
        return _mm(w, vt, mm).transpose(1, 0, 2)                # [blk,n,D]

    out = jax.lax.map(one, jnp.arange(0, t, block))
    return out.reshape(t, n, d)


def layer_forward(h, p, pos, sizes, mode: str, windowed: bool,
                  turned: bool):
    """One layer on ``h`` [T, H] float32 at positions ``pos`` [T]."""
    mm, eps, t = head_mode(mode), sizes["norm_eps"], h.shape[0]
    n, nkv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    scores = _mm(h, p["router"], mm)
    a = _rms(h, p["in_norm"], eps)
    q = _mm(a, p["wq"], mm).reshape(t, n, d)
    k = _mm(a, p["wk"], mm).reshape(t, nkv, d)
    v = _mm(a, p["wv"], mm).reshape(t, nkv, d)
    if turned:
        q = _rotary(q, pos, sizes["rope_theta"])
        k = _rotary(k, pos, sizes["rope_theta"])
    k, v = (jnp.repeat(x, n // nkv, axis=1) for x in (k, v))
    band = sizes["window"] if windowed and mode != "full_context" else None
    o = attention(q, k, v, band, mm).reshape(t, n * d)
    h = h + _mm(o, p["wo"], mm)
    m = _rms(h, p["post_norm"], eps)
    if mode == "router_post_norm":
        scores = _mm(m, p["router"], mm)
    probs = jax.nn.softmax(scores, axis=-1)
    top, chosen = jax.lax.top_k(probs, sizes["top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    act = jax.nn.silu if mode == "silu_experts" else jax.nn.relu

    def one_expert(acc, e_w):
        e, wg, wu, wd = e_w
        gate = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        y = _mm(act(_mm(m, wg, mm)) * _mm(m, wu, mm), wd, mm)
        return acc + gate[:, None] * y, None

    experts = jnp.arange(p["w_gate"].shape[0])
    moe, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (experts, p["w_gate"], p["w_up"], p["w_down"]))
    return h + moe


@functools.partial(jax.jit,
                   static_argnames=("sizes", "mode", "windowed", "turned"))
def _layer_jit(h, p, pos, sizes, mode, windowed, turned):
    return layer_forward(h, p, pos, dict(sizes), mode, windowed, turned)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head_logits(h, final_norm, head, eps, mode):
    return _mm(_rms(h, final_norm, eps), head, head_mode(mode))


def forward_logits(weights, ids, sizes, layouts, mode: str = "float32"):
    """Logits [T, V] of one sequence from position 0 (small sizes).
    ``layouts``: ``(sliding_window_layout, rope_layout)``, a 0 / 1 a
    layer."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    h = weights["embed"][ids].astype(jnp.float32)
    for p, windowed, turned in zip(weights["layers"], *layouts):
        h = _layer_jit(h, p, pos, sizes_key(sizes), mode, bool(windowed),
                       bool(turned))
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], mode)
