"""Plain reference for a decoder that mixes Mamba-1 layers and attention
layers (AI21-Jamba2-3B, ``model_type: jamba``): the forward in
straightforward ``jax.numpy``, float32, matmuls at ``highest``.  No cache, no
state kept between calls, no batching, no kernel, and nothing imported from
the program: the selective scan is a sequential ``lax.scan`` over the
positions, the convolution ``d_conv`` shifted products, attention the plain
causal softmax.

For a layer's input ``h`` [T, H], ``x = rmsnorm(h)``::

    Mamba layer:
      [u, z]    = x W_in                               each [T, C], C = expand * H
      c[t]      = silu(b_c + sum_j w_c[j] * u[t - (K-1) + j])       u[<0] = 0
      [r, B, C] = c W_x         [T, R], [T, N], [T, N]; each through an RMSNorm
      dt        = softplus(r W_dt + b_dt)              [T, C]
      s[t]      = exp(dt[t] (x) A) * s[t-1] + (dt[t] * c[t]) (x) B[t]     A = -exp(A_log) [C, N]
      y[t]      = s[t] C[t] + D * c[t]
      h         = h + (y * silu(z)) W_out
    Attention layer: q = x Wq [T, n, d], k = x Wk, v = x Wv [T, kv, d];
      o[t, a] = softmax_{i <= t}(q[t, a] . k[i] / sqrt(d)) v[i];  h = h + concat(o) Wo
    Every layer then: h = h + (silu(x' W_gate) * (x' W_up)) W_down,  x' = rmsnorm(h)
    End: logits = rmsnorm(h) E^T  (E the embedding: tied)

``mode``: ``float32`` is the reference proper; ``fp8`` is the control for the
stated bfloat16 (matmul operands through float8_e4m3fn with a per-tensor
scale, as ``reference._mm``); ``bf16_state`` is the other control: the scan's
state rounded to bfloat16 wherever a serving program that held it so would
round it, at the end of the prompt and after every later position,
everything else float32.

Departures from the release, each also under the configuration's
``assumed``: ``config.json`` has no key for a position term in the
attention layers (none: the Mamba layers carry position), for the head size
(hidden / heads), for the RMSNorm on ``r``, ``B`` and ``C`` (the Jamba
family's addition, ``rms_norm_eps``), nor for ``softplus``; they are read
from the family's published modelling code.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms, head_logits  # noqa: F401
from .reference import _mm


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the control of the state keeps
    float32 matmuls)."""
    return mode if mode in ("float32", "fp8") else "float32"


def selective_scan(dt, c, b, cm, a, d, prompt_len, state_dtype):
    """``y`` [T, C]: one position after the other from an empty state
    ``[C, N]``.  ``state_dtype`` other than float32: the state rounded to it
    after position ``prompt_len - 1`` and after every later one."""
    t = dt.shape[0]
    store = lambda s: s.astype(state_dtype).astype(jnp.float32)

    def one(s, x):
        i, dt_t, c_t, b_t, cm_t = x
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * c_t)[:, None] * b_t
        if state_dtype != jnp.float32:
            s = jnp.where(i >= prompt_len - 1, store(s), s)
        y = jnp.sum(s * cm_t, axis=-1) + d * c_t
        return s, y

    s0 = jnp.zeros(a.shape, jnp.float32)
    _, y = jax.lax.scan(one, s0, (jnp.arange(t), dt, c, b, cm))
    return y


def mamba_mixer(x, p, sizes, mode, prompt_len):
    mm = head_mode(mode)
    t = x.shape[0]
    c_, n, k = sizes["d_inner"], sizes["d_state"], sizes["d_conv"]
    r_, eps = sizes["dt_rank"], sizes["norm_eps"]
    uz = _mm(x, p["w_in"], mm)
    u, z = uz[:, :c_], uz[:, c_:]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    c = p["conv_b"]
    for j in range(k):
        c = c + p["conv_w"][j] * padded[j:j + t]
    c = jax.nn.silu(c)
    rbc = _mm(c, p["w_x"], mm)
    r = _rms(rbc[:, :r_], p["dt_norm"], eps)
    b = _rms(rbc[:, r_:r_ + n], p["b_norm"], eps)
    cm = _rms(rbc[:, r_ + n:], p["c_norm"], eps)
    dt = jax.nn.softplus(_mm(r, p["w_dt"], mm) + p["b_dt"])
    y = selective_scan(dt, c, b, cm, -jnp.exp(p["A_log"]), p["D"],
                       prompt_len,
                       jnp.bfloat16 if mode == "bf16_state" else jnp.float32)
    return _mm(y * jax.nn.silu(z), p["w_out"], mm)


def attention_mixer(x, p, sizes, mode):
    mm = head_mode(mode)
    t = x.shape[0]
    n, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    q = _mm(x, p["wq"], mm).reshape(t, n, d).transpose(1, 0, 2)
    k = jnp.repeat(_mm(x, p["wk"], mm).reshape(t, kv, d), n // kv, axis=1)
    v = jnp.repeat(_mm(x, p["wv"], mm).reshape(t, kv, d), n // kv, axis=1)
    s = _mm(q, k.transpose(1, 2, 0), mm) / math.sqrt(d)        # [n, T, T]
    at = jnp.arange(t)
    s = jnp.where(at[None, :, None] >= at[None, None, :], s, -jnp.inf)
    o = _mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2), mm)
    return _mm(o.transpose(1, 0, 2).reshape(t, n * d), p["wo"], mm)


def layer_forward(h, p, sizes, mode: str, prompt_len=None):
    """One layer on ``h`` [T, H] float32; the layer's kind is what its
    weights are."""
    mm, eps = head_mode(mode), sizes["norm_eps"]
    x = _rms(h, p["in_norm"], eps)
    if "w_in" in p:
        h = h + mamba_mixer(x, p, sizes, mode, prompt_len)
    else:
        h = h + attention_mixer(x, p, sizes, mode)
    m = _rms(h, p["post_norm"], eps)
    return h + _mm(jax.nn.silu(_mm(m, p["w_gate"], mm))
                   * _mm(m, p["w_up"], mm), p["w_down"], mm)


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, p, pos, prompt_len, sizes, mode):
    """``pos`` is taken and not used (no layer has a position term): the
    signature is the other references'."""
    del pos
    return layer_forward(h, p, dict(sizes), mode, prompt_len)


def forward_logits(weights, ids, sizes, mode: str = "float32",
                   prompt_len: int = 0):
    """Logits [T, V] of one sequence from position 0 (small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    h = weights["embed"][ids].astype(jnp.float32)
    for p in weights["layers"]:
        h = _layer_jit(h, p, None, jnp.asarray(prompt_len, jnp.int32),
                       sizes_key(sizes), mode)
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], head_mode(mode))
