"""Plain reference for the latent-attention decoder with routed experts and
a shared expert (A.X-K1, ``model_type: axk1``; the DeepSeek-V3 family): the
forward in straightforward ``jax.numpy``, float32, matmuls at ``highest``.
The EXPANDED form of latent attention only, the plain causal softmax (a
block of queries at a time so that 9,216 positions fit; every block sees
the whole key array), the router as written below.  No cache, no absorbed
form, no kernel, no batching, and nothing imported from the program.

For a layer's input ``h`` [T, H], ``x = rmsnorm(h)``, ``n`` heads::

    c_q        = rmsnorm(x W_DQ)                       [T, q_rank]
    [q_n, q_r] = c_q W_UQ                              [T, n, dn], [T, n, dr]
    [c, k_r]   = x W_DKV                               [T, r], [T, dr]
    c          = rmsnorm(c);  k_r is ONE head, shared by all n
    q_r, k_r   = rotary(q_r), rotary(k_r)     interleaved pairs; YaRN: per
                 pair a blend of theta_i and theta_i / factor by the linear
                 ramp between the pairs that turn beta_fast and beta_slow
                 times in ``original`` positions; cos / sin scale
                 mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    [k_n, v]   = c W_UKV                               [T, n, dn], [T, n, dv]
    s[t,i,a]   = (q_n[t,a] . k_n[i,a] + q_r[t,a] . k_r[i]) * (dn + dr)^-0.5 * m^2
                 m = 0.1 * mscale_all_dim * ln(factor) + 1
    o[t,a]     = sum_{i<=t} softmax_i(s[t,i,a]) v[i,a];  h = h + concat_a(o) W_O
    dense layer:   h = h + (silu(x' W_gate) * (x' W_up)) W_down,  x' = rmsnorm(h)
    expert layer, m = rmsnorm(h):
      s   = sigmoid(m W_r)                             [T, E]
      the E experts are n_group groups of consecutive ones; a group's score
      is the sum of its 2 largest s; the topk_group best groups stay; the
      top_k largest s among their experts are chosen
      g_e = s_e / (sum of the chosen s + 1e-20) * routed_scale
      h   = h + sum over the chosen e HELD HERE of g_e E_e(m) + E_shared(m)
    End: logits = rmsnorm(h) W_head   (over the rows of the vocabulary held)

This is one chip's share: of the E experts the router scores, ``held`` from
``first_held`` have weights here, and a token routed elsewhere adds nothing
(its holder adds it); the shared expert is whole.

``mode``: ``float32`` is the reference proper; ``fp8`` is the control for
the stated bfloat16 (matmul operands through float8_e4m3fn, as
``reference._mm``); ``no_group_limit`` is the other control: float32, the
router's group limit ignored (plain top_k of all E).

Departures from the release, each also under the configuration's
``assumed``: ``topk_method: "none"`` is read as the DeepSeek-V3 rule without
the bias term (the config gives ``n_group`` and ``topk_group``, which only
group-limited selection reads, and ``scoring_func: sigmoid``, for which the
family's group score is the sum of the two largest); rotary on interleaved
pairs; the 1e-20; the seeded initialiser.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .blockdiff_reference import _rms, head_logits  # noqa: F401
from .reference import _mm

QUERY_BLOCK = 256


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def head_mode(mode: str) -> str:
    """The matmul precision of a mode (the router's control keeps float32
    matmuls)."""
    return "fp8" if mode == "fp8" else "float32"


def mscale(factor: float, scale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def yarn_inv_freq(sizes: dict):
    """The ``rope / 2`` inverse frequencies (float32)."""
    dim, theta = sizes["rope"], sizes["rope_theta"]
    factor, original = sizes["rope_factor"], sizes["rope_original"]
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 1:
        return plain

    def pair_that_turns(times):
        return dim * math.log(original / (times * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(sizes["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(sizes["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary(x, pos, sizes):
    """``x`` [T, ..., dr] at positions ``pos`` [T]: pairs ``(2i, 2i + 1)``
    turned by ``pos * inv_freq[i]``."""
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * yarn_inv_freq(sizes)
    scale = mscale(sizes["rope_factor"], sizes["mscale"]) \
        / mscale(sizes["rope_factor"], sizes["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(x, p, pos, sizes, mm):
    t = x.shape[0]
    n, dn, dr, dv = sizes["num_heads"], sizes["nope"], sizes["rope"], \
        sizes["v_dim"]
    r, eps = sizes["kv_rank"], sizes["norm_eps"]
    c_q = _rms(_mm(x, p["w_dq"], mm), p["q_norm"], eps)
    q = _mm(c_q, p["w_uq"], mm).reshape(t, n, dn + dr)
    ck = _mm(x, p["w_dkv"], mm)
    c = _rms(ck[:, :r], p["kv_norm"], eps)
    k_r = rotary(ck[:, r:], pos, sizes)                       # [T, dr]
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], pos, sizes)], -1)
    kv = _mm(c, p["w_ukv"], mm).reshape(t, n, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (t, n, dr))], -1)
    v = kv[..., dn:]
    m = mscale(sizes["rope_factor"], sizes["mscale_all_dim"]) \
        if sizes["mscale_all_dim"] else 1.0
    scale = (dn + dr) ** -0.5 * m * m
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)     # [n, d, T] [n, T, dv]
    blk = min(QUERY_BLOCK, t)
    if t % blk:
        blk = t

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk).transpose(1, 0, 2)
        s = _mm(qb, kt, mm) * scale                           # [n, blk, T]
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vt, mm)        # [n, blk, dv]

    o = jax.lax.map(block, jnp.arange(0, t, blk))             # [T/blk, n, blk, dv]
    o = o.transpose(0, 2, 1, 3).reshape(t, n * dv)
    return _mm(o, p["w_o"], mm)


def route(scores, sizes, group_limit: bool = True):
    """``(gates [T, k], experts [T, k])`` from sigmoid ``scores`` [T, E]."""
    t, e = scores.shape
    g, k = sizes["n_group"], sizes["top_k"]
    eligible = scores
    if group_limit and g > 1:
        by_group = scores.reshape(t, g, e // g)
        group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
        cut = jnp.sort(group_score, axis=-1)[:, g - sizes["topk_group"]]
        keep = group_score >= cut[:, None]                    # [T, g]
        eligible = jnp.where(keep[:, :, None], by_group, -1.0).reshape(t, e)
    top, experts = jax.lax.top_k(eligible, k)
    return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"], experts


def gated(m, w_gate, w_up, w_down, mm):
    return _mm(jax.nn.silu(_mm(m, w_gate, mm)) * _mm(m, w_up, mm), w_down, mm)


def experts_layer(m, p, sizes, mode):
    mm = head_mode(mode)
    scores = jax.nn.sigmoid(_mm(m, p["router"], mm))
    gates, chosen = route(scores, sizes, mode != "no_group_limit")
    out = gated(m, p["s_gate"], p["s_up"], p["s_down"], mm)
    for j in range(sizes["held"]):
        gate = jnp.sum(jnp.where(chosen == sizes["first_held"] + j, gates,
                                 0.0), axis=-1)
        out = out + gate[:, None] * gated(m, p["e_gate"][j], p["e_up"][j],
                                          p["e_down"][j], mm)
    return out


def layer_forward(h, p, pos, sizes, mode: str):
    """One layer on ``h`` [T, H] float32; the layer's kind is what its
    weights are."""
    mm, eps = head_mode(mode), sizes["norm_eps"]
    h = h + attention(_rms(h, p["in_norm"], eps), p, pos, sizes, mm)
    m = _rms(h, p["post_norm"], eps)
    if "router" in p:
        return h + experts_layer(m, p, sizes, mode)
    return h + gated(m, p["w_gate"], p["w_up"], p["w_down"], mm)


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, p, pos, prompt_len, sizes, mode):
    """``prompt_len`` is taken and not used (nothing here depends on where
    the prompt ends): the signature is the other references'."""
    del prompt_len
    return layer_forward(h, p, pos, dict(sizes), mode)


def forward_logits(weights, ids, sizes, mode: str = "float32"):
    """Logits [T, V] of one sequence from position 0 (small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    h = weights["embed"][ids].astype(jnp.float32)
    for p in weights["layers"]:
        h = _layer_jit(h, p, pos, None, sizes_key(sizes), mode)
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], head_mode(mode))
