"""Plain reference for an attention-free decoder whose layers are gated power
retention of degree 2 (Brumby-14B-Base, ``model_type: brumby``): the forward
in straightforward ``jax.numpy``, float32, matmuls at ``highest``.  No
cache, no state, no batching, no kernel, and nothing imported from the
program: the token mixer is the QUADRATIC form, every position against every
earlier one, computed for a block of query positions at a time so that 4,864
positions fit the chip.

One layer on ``h`` [T, H], K/V head ``j``, its query heads ``a``::

    x  = rmsnorm(h);   q, k = rope(rmsnorm_head(x Wq)), rope(rmsnorm_head(x Wk));   v = x Wv
    lg = log_sigmoid(x Wg + bg)                               [T, Hkv]
    w[t, i] = ((q[t, a] . k[i, j]) / sqrt(D))^2 * exp(sum_{u=i+1..t} lg[u, j])   (i <= t)
    y[t, a] = sum_i w[t, i] v[i, j] / (sum_i w[t, i] + eps)
    h = h + concat(y) Wo;   h = h + (silu(x' W1) * (x' W3)) W2,   x' = rmsnorm(h)

``mode``: ``float32`` is the reference proper; ``fp8`` is the control for the
stated bfloat16 (matmul operands through float8_e4m3fn with a per-tensor
scale, as ``reference._mm``); ``no_gate`` drops the decay (``lg = 0``): a
skipped gate.  :func:`retention_recurrent` is the other control, the SAME
function computed as a recurrence whose state is rounded to ``state_dtype``
wherever a serving program would hold it (after each chunk of 128 prompt
positions, after each later position); with float32 it agrees with the
quadratic form, with bfloat16 it is what a state held in bfloat16 would
serve.  Its ``phi`` is the whole outer square (16,384 entries): the
control's layout is its own affair.

Departures from the release, each also under the configuration's
``assumed``: ``config.json`` has no key for the degree (2), the gate (a
linear map with bias of the layer's normed input to one value a K/V head,
through log-sigmoid), the normaliser (the output divided by the sum of its
weights plus ``eps``), QK-norm and the halves-rotated rotary turn (the Qwen3
block's); they are read from the release's description of power retention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms, _rotary, head_logits  # noqa: F401
from .reference import _mm

QUERY_ROWS = 512        # query positions a block of the quadratic form
STATE_CHUNK = 128       # prompt positions between two roundings of the
                        # control's state
STEP_WINDOW = 1024      # positions the control takes one at a time: a
                        # prompt's last part chunk and the served tokens


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def projections(h, p, pos, sizes, mode, gated: bool = True):
    """``q`` [T, n, D] (scaled by ``D ** -0.5``), ``k``, ``v`` [T, kv, D]
    and ``lg`` [T, kv] of one layer's input ``h`` [T, H]."""
    n, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    eps, t = sizes["norm_eps"], h.shape[0]
    x = _rms(h, p["in_norm"], eps)
    q = _mm(x, p["wq"], mode).reshape(t, n, d)
    k = _mm(x, p["wk"], mode).reshape(t, kv, d)
    v = _mm(x, p["wv"], mode).reshape(t, kv, d)
    q = _rotary(_rms(q, p["q_norm"], eps), pos, sizes["rope_theta"])
    k = _rotary(_rms(k, p["k_norm"], eps), pos, sizes["rope_theta"])
    lg = jax.nn.log_sigmoid(_mm(x, p["wg"], mode) + p["bg"])
    if not gated:
        lg = jnp.zeros_like(lg)
    return q / math.sqrt(d), k, v, lg


def retention_quadratic(q, k, v, lg, eps, mode):
    """``y`` [T, n, D]: every query position against every earlier one, a
    block of ``QUERY_ROWS`` query positions at a time."""
    t, n, d = q.shape
    g = n // k.shape[1]
    kr = jnp.repeat(k, g, axis=1).transpose(1, 2, 0)         # [n, D, T]
    vr = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)         # [n, T, D]
    cum = jnp.repeat(jnp.cumsum(lg, axis=0), g, axis=1).T    # [n, T]
    at = jnp.arange(t)

    def block(start):
        rows = jnp.minimum(start + jnp.arange(QUERY_ROWS), t - 1)
        s = _mm(q[rows].transpose(1, 0, 2), kr, mode)        # [n, R, T]
        decay = jnp.where(at[None, :] <= rows[:, None],
                          cum[:, rows, None] - cum[:, None, :], -jnp.inf)
        w = jnp.square(s) * jnp.exp(decay)
        y = _mm(w, vr, mode) / (jnp.sum(w, -1, keepdims=True) + eps)
        return y.transpose(1, 0, 2)                          # [R, n, D]

    starts = jnp.arange(0, t, QUERY_ROWS)
    return jax.lax.map(block, starts).reshape(-1, n, d)[:t]


def _outer(u):
    """``phi(u)``: the whole outer square, ``[..., D] -> [..., D * D]``."""
    return (u[..., :, None] * u[..., None, :]).reshape(
        u.shape[:-1] + (u.shape[-1] ** 2,))


def retention_recurrent(q, k, v, lg, eps, prompt_len, state_dtype):
    """The control: the same function as a recurrence over ``S`` [kv, D*D,
    D] and ``z`` [kv, D*D], each rounded to ``state_dtype`` after every
    whole chunk of ``STATE_CHUNK`` prompt positions and after every position
    from there on.  ``prompt_len`` is traced: one compile a length."""
    t, n, d = q.shape
    kv = k.shape[1]
    g = n // kv
    hp = jax.lax.Precision.HIGHEST
    c = STATE_CHUNK
    n_chunks = t // c
    whole = prompt_len // c                      # chunks taken as chunks
    qg = q.reshape(t, kv, g, d)
    store = lambda x: x.astype(state_dtype).astype(jnp.float32)

    def chunk(carry, i):
        s, z = carry
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * c, c, 0)
        qc, kc, vc, lgc = sl(qg), sl(k), sl(v), sl(lg)
        cum = jnp.cumsum(lgc, axis=0)                        # [c, kv]
        sc = jnp.einsum("tjad,ijd->jati", qc, kc, precision=hp)
        low = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        decay = jnp.where(low, cum.T[:, :, None] - cum.T[:, None, :],
                          -jnp.inf)                          # [kv, c, c]
        w = jnp.square(sc) * jnp.exp(decay)[:, None]
        num = jnp.einsum("jati,ijd->tjad", w, vc, precision=hp)
        den = jnp.sum(w, -1).transpose(2, 0, 1)              # [c, kv, g]
        fq, fk = _outer(qc), _outer(kc)
        since = jnp.exp(cum)[:, :, None]                     # [c, kv, 1]
        num = num + since[..., None] * jnp.einsum(
            "tjaP,jPd->tjad", fq, s, precision=hp)
        den = den + since * jnp.einsum("tjaP,jP->tja", fq, z, precision=hp)
        left = jnp.exp(cum[-1][None] - cum)                  # [c, kv]
        s_new = jnp.exp(cum[-1])[:, None, None] * s + jnp.einsum(
            "ijP,ijd->jPd", fk * left[..., None], vc, precision=hp)
        z_new = jnp.exp(cum[-1])[:, None] * z + jnp.einsum(
            "ijP,ij->jP", fk, left, precision=hp)
        live = i < whole
        y = num / (den[..., None] + eps)
        return (jnp.where(live, store(s_new), s),
                jnp.where(live, store(z_new), z)), y

    s0 = jnp.zeros((kv, d * d, d), jnp.float32)
    z0 = jnp.zeros((kv, d * d), jnp.float32)
    (s, z), y_chunks = jax.lax.scan(chunk, (s0, z0), jnp.arange(n_chunks))
    y = jnp.pad(y_chunks.reshape(n_chunks * c, kv, g, d),
                ((0, t - n_chunks * c),) + ((0, 0),) * 3)

    # the positions after the last whole chunk of the prompt, one at a
    # time, in a window of fixed length (one compile whatever the lengths)
    span = min(t, STEP_WINDOW)
    start = jnp.minimum(whole * c, t - span)
    win = lambda x: jax.lax.dynamic_slice_in_dim(x, start, span, 0)

    def one(carry, x):
        s, z = carry
        i, qi, ki, vi, lgi, was = x
        gi = jnp.exp(lgi)
        fk = _outer(ki)
        s_new = store(gi[:, None, None] * s + fk[..., None] * vi[:, None])
        z_new = store(gi[:, None] * z + fk)
        fq = _outer(qi)                                      # [kv, g, P]
        num = jnp.einsum("jaP,jPd->jad", fq, s_new, precision=hp)
        den = jnp.einsum("jaP,jP->ja", fq, z_new, precision=hp)
        live = i >= whole * c
        return (jnp.where(live, s_new, s), jnp.where(live, z_new, z)), \
            jnp.where(live, num / (den[..., None] + eps), was)

    _, y_steps = jax.lax.scan(
        one, (s, z), (start + jnp.arange(span), win(qg), win(k), win(v),
                      win(lg), win(y)))
    y = jax.lax.dynamic_update_slice_in_dim(y, y_steps, start, 0)
    return y.reshape(t, n, d)


def layer_forward(h, p, pos, sizes, mode: str, prompt_len=None):
    """One layer on ``h`` [T, H] float32 at positions ``pos`` [T].  Mode
    ``bf16_state`` takes the token mixer as :func:`retention_recurrent`
    with a bfloat16 state, everything else in float32."""
    eps = sizes["norm_eps"]
    mm = mode if mode in ("float32", "fp8") else "float32"
    q, k, v, lg = projections(h, p, pos, sizes, mm, mode != "no_gate")
    if mode == "bf16_state":
        y = retention_recurrent(q, k, v, lg, sizes["eps"], prompt_len,
                                jnp.bfloat16)
    else:
        y = retention_quadratic(q, k, v, lg, sizes["eps"], mm)
    h = h + _mm(y.reshape(h.shape[0], -1), p["wo"], mm)
    m = _rms(h, p["post_norm"], eps)
    return h + _mm(jax.nn.silu(_mm(m, p["w_gate"], mm))
                   * _mm(m, p["w_up"], mm), p["w_down"], mm)


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, p, pos, prompt_len, sizes, mode):
    return layer_forward(h, p, pos, dict(sizes), mode, prompt_len)


def forward_hidden(layer_of, num_layers: int, embed, ids, sizes,
                   mode: str = "float32", prompt_len: int = 0):
    """Hidden states [T, H] before the final norm, a layer's weights held
    at a time (``layer_of(i)``)."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    h = embed[ids].astype(jnp.float32)
    for i in range(num_layers):
        h = _layer_jit(h, layer_of(i), pos,
                       jnp.asarray(prompt_len, jnp.int32),
                       sizes_key(sizes), mode)
    return h


def forward_logits(weights, ids, sizes, mode: str = "float32",
                   prompt_len: int = 0):
    """Logits [T, V] of one sequence from position 0 (small sizes)."""
    layers = weights["layers"]
    h = forward_hidden(lambda i: layers[i], len(layers), weights["embed"],
                       ids, sizes, mode, prompt_len)
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], head_mode(mode))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the controls of the token mixer
    keep float32 matmuls)."""
    return mode if mode in ("float32", "fp8") else "float32"
