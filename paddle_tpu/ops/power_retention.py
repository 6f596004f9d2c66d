"""Gated power retention of degree 2: the attention-free token mixer of the
``brumby`` family, in the forms a serving engine needs.

For one K/V head ``j`` (its group of query heads ``a``), gate ``g[t] =
exp(lg[t])`` in (0, 1] and positions ``i <= t``::

    w[t, i] = (scale * q[t, a] . k[i, j])^2 * prod_{u = i+1..t} g[u]
    y[t, a] = sum_i w[t, i] v[i, j] / (sum_i w[t, i] + eps)

(``scale`` is ``head_dim ** -0.5``; it goes onto ``phi(q)`` squared, in
float32, so that q itself is never rounded for it).  With ``phi(u)``
the symmetric square of ``u`` (``phi(a) . phi(b) == (a . b)^2``) this is a
recurrence over a state of constant size::

    S[t] = g[t] S[t-1] + v[t] phi(k[t])^T        z[t] = g[t] z[t-1] + phi(k[t])
    y[t, a] = S[t] phi(q[t, a]) / (z[t] . phi(q[t, a]) + eps)

**The state's layout.**  ``phi`` is held in 16 x 16 tiles: the ``d``
channels are ``d / 16`` groups, and for every pair of groups ``I <= J`` the
tile ``u_I u_J^T`` (256 entries, times sqrt(2) off the diagonal), ``D =
256 * n (n + 1) / 2`` entries in all (9,216 for 128 channels; the packed
triangle would be 8,256 and the whole square 16,384).  A tile is two whole
rows of 128 lanes, so ``phi`` is slices, broadcasts and products, or two
matmuls with 0/1 matrices and a product, with no gather.  ``S`` is ``[B, Hkv, dv, D]`` and ``z`` ``[B, Hkv, 1, D]``, float32,
``D`` innermost: a step's rank-one update broadcasts ``phi(k)`` down the
rows and ``v`` along them, and its read reduces along them.

**Three forms that must agree** (``tests/test_power_retention.py``):

- :func:`power_retention_chunked`: a scan over chunks of positions; inside
  a chunk the quadratic form under the decay mask, between chunks the
  state.  It continues from any state.  Positions where ``keep`` is false
  are identity steps (``phi(k) = 0``, ``lg = 0``): a padded bucket leaves
  the state of the true prompt.
- :func:`power_retention_prefill`, the prefill as a server runs it, from an
  EMPTY state: on a TPU the outputs are the quadratic form in a Pallas
  kernel that writes no score to memory and the state is taken from
  ``phi(k)`` alone; elsewhere it is the chunked form from zeros.
- :func:`power_retention_step`, the decode step: one token a row, the state
  read and written once.  On a TPU it is a Pallas kernel over ``(row, K/V
  head, tile of D)`` with ``S`` and ``z`` aliased in and out; elsewhere, or
  where Mosaic cannot tile the shapes (:func:`step_kernel_refusal`), the
  same arithmetic as an XLA composition.  Rows where ``keep`` is false are
  identity steps too, so a pool never has to restore a free slot's state.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError

__all__ = ["PHI_TILE", "phi_size", "symmetric_square", "chunk_length",
           "power_retention_chunked", "power_retention_prefill",
           "power_retention_step", "prefill_kernel_refusal",
           "power_retention_quadratic", "step_kernel_refusal",
           "step_tile", "state_bytes"]

PHI_TILE = 16           # channels a group; a tile is PHI_TILE^2 entries
CHUNK = 128             # positions a chunk of the prefill scan, at most
EPS = 1e-6              # added to the sum of weights before the division
_VMEM_STEP_BYTES = 6 << 20      # one block of S the step kernel holds


def phi_size(d: int) -> int:
    """Entries of ``phi`` for ``d`` channels (a multiple of 16)."""
    if d % PHI_TILE:
        raise InvalidArgumentError(
            "power retention holds phi in 16 x 16 tiles: head_dim %d is "
            "not a multiple of 16" % d)
    n = d // PHI_TILE
    return PHI_TILE * PHI_TILE * n * (n + 1) // 2


@functools.lru_cache(maxsize=None)
def _pairs(n: int) -> tuple:
    return tuple((i, j) for i in range(n) for j in range(i, n))


@functools.lru_cache(maxsize=None)
def _expanders(d: int):
    """``(left [d, D], right [d, D], scale [D])`` as numpy: entry ``l`` of
    ``phi(u)`` is ``scale[l] * u[c] * u[e]`` for the one ``c`` with
    ``left[c, l] == 1`` and the one ``e`` with ``right[e, l] == 1``."""
    import numpy as np

    n, t = d // PHI_TILE, PHI_TILE
    left = np.zeros((d, phi_size(d)), np.float32)
    right = np.zeros_like(left)
    scale = np.ones((phi_size(d),), np.float32)
    for p, (i, j) in enumerate(_pairs(n)):
        for a in range(t):
            at = p * t * t + a * t
            left[i * t + a, at:at + t] = 1.0
            right[j * t + np.arange(t), at + np.arange(t)] = 1.0
        if i != j:
            scale[p * t * t:(p + 1) * t * t] = math.sqrt(2.0)
    return left, right, scale


PHI_MATMUL_ROWS = 1024      # rows up to which phi goes through matmuls


def _phi_by_matmul(u):
    """``phi`` with the MXU doing the shuffle: ``u`` times two 0/1 matrices
    spreads the channels over the ``D`` lanes (exact: one product a lane,
    bfloat16 operands as they are, float32 at ``highest``), and one
    elementwise product finishes it, every array lane-dense."""
    left, right, scale = _expanders(u.shape[-1])
    exact = jax.lax.Precision.HIGHEST if u.dtype == jnp.float32 else None

    def spread(e):
        return jnp.matmul(u, jnp.asarray(e, u.dtype), precision=exact,
                          preferred_element_type=jnp.float32)

    return spread(left) * spread(right) * scale


def _phi_by_broadcast(u):
    """``phi`` as slices, broadcasts and products of the 16 x 16 tiles."""
    d = u.shape[-1]
    n = d // PHI_TILE
    g = u.astype(jnp.float32).reshape(u.shape[:-1] + (n, PHI_TILE))
    pairs = _pairs(n)
    left = jnp.stack([g[..., i, :] for i, _ in pairs], axis=-2)
    right = jnp.stack([g[..., j, :] for _, j in pairs], axis=-2)
    scale = jnp.asarray([1.0 if i == j else math.sqrt(2.0)
                         for i, j in pairs], jnp.float32)
    tiles = left[..., :, None] * right[..., None, :] * scale[:, None, None]
    return tiles.reshape(u.shape[:-1] + (phi_size(d),))


def symmetric_square(u):
    """``phi(u)`` ``[..., d] -> [..., D]`` in float32, tiled as the module
    docstring says.  Exact: ``phi(a) . phi(b) == (a . b)^2``.  Two ways to
    the same numbers, chosen from the number of rows: few rows (a decode
    step's) go through two small matmuls, whose results are lane-dense;
    many (a prefill chunk's) through broadcasts, which cost no FLOPs."""
    rows = math.prod(u.shape[:-1])
    if u.dtype in (jnp.float32, jnp.bfloat16) and rows <= PHI_MATMUL_ROWS:
        return _phi_by_matmul(u)
    return _phi_by_broadcast(u)


def chunk_length(length: int) -> int:
    """Positions a chunk of the prefill scan, from the sequence's length:
    128 (one MXU tile of scores; the work inside a chunk grows with it and
    the work between chunks does not shrink), or the whole of a shorter
    sequence."""
    return max(1, min(int(length), CHUNK))


def _grouped(q, hkv: int):
    """``[B, Hq, T, d] -> [B, Hkv, G, T, d]``: query head ``a`` reads K/V
    head ``a // G``."""
    b, hq, t, d = q.shape
    if hq % hkv:
        raise InvalidArgumentError(
            "%d query heads are not a whole multiple of %d K/V heads"
            % (hq, hkv))
    return q.reshape(b, hkv, hq // hkv, t, d)


def power_retention_quadratic(q, k, v, log_gate, scale: float = 1.0,
                              eps: float = EPS):
    """The definition, all positions against all: ``q`` ``[B, Hq, T, d]``,
    ``k``, ``v`` ``[B, Hkv, T, d]``, ``log_gate`` ``[B, Hkv, T]`` float32,
    ``scale`` on ``q . k`` before the square.  O(T^2); what the two served
    forms are tested against at small sizes."""
    hkv = k.shape[1]
    qg = _grouped(q.astype(jnp.float32), hkv)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    t = q.shape[2]
    s = scale * jnp.einsum("bhgtd,bhid->bhgti", qg, kf,
                           precision=jax.lax.Precision.HIGHEST)
    b = jnp.cumsum(log_gate.astype(jnp.float32), axis=-1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    decay = jnp.where(causal, b[..., :, None] - b[..., None, :], -jnp.inf)
    w = jnp.square(s) * jnp.exp(decay)[:, :, None]
    num = jnp.einsum("bhgti,bhid->bhgtd", w, vf,
                     precision=jax.lax.Precision.HIGHEST)
    y = num / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return y.reshape(q.shape[:3] + (v.shape[-1],))


def power_retention_chunked(q, k, v, log_gate, state, norm, keep=None,
                            chunk: Optional[int] = None,
                            scale: float = 1.0, eps: float = EPS):
    """The prefill form.  ``q`` ``[B, Hq, T, d]``, ``k``, ``v``
    ``[B, Hkv, T, d]``, ``log_gate`` ``[B, Hkv, T]``; ``state`` ``[B, Hkv,
    dv, D]`` and ``norm`` ``[B, Hkv, 1, D]`` are what the positions before
    these left (zeros for a fresh prompt); ``keep`` ``[B, T]`` marks the
    real positions; ``scale`` multiplies ``q . k`` before the square.
    Returns ``(y [B, Hq, T, dv] in v's type, state, norm)``.  The matmuls
    between chunks multiply in ``k``'s type and
    accumulate in float32; everything else is float32."""
    b, hq, t, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    c = chunk_length(t) if chunk is None else int(chunk)
    if c < 1:
        raise InvalidArgumentError("chunk must be >= 1, got %r" % (chunk,))
    lg = log_gate.astype(jnp.float32)
    if keep is None:
        keep = jnp.ones((b, t), bool)
    pad = -t % c
    if pad:
        def padded(x, axis):
            width = [(0, 0)] * x.ndim
            width[axis] = (0, pad)
            return jnp.pad(x, width)
        q, k, v = padded(q, 2), padded(k, 2), padded(v, 2)
        lg, keep = padded(lg, 2), padded(keep, 1)
    n = (t + pad) // c
    live = keep[:, None, :]
    lg = jnp.where(live, lg, 0.0)
    kf = jnp.where(live[..., None], k.astype(jnp.float32), 0.0)

    def chunks(x, axis):          # the chunk axis first, for the scan
        shape = x.shape[:axis] + (n, c) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    xs = (chunks(_grouped(q.astype(jnp.float32), hkv), 3), chunks(kf, 2),
          chunks(v.astype(jnp.float32), 2), chunks(lg, 2))
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    # the type the matmuls between chunks multiply in: the activations'
    # (bfloat16 as served: the chip multiplies float32 operands as
    # bfloat16 anyway, so this halves what phi moves and no product;
    # float32 in the CPU tests), accumulated in float32
    mm = k.dtype
    acc = {"preferred_element_type": jnp.float32}

    def one(carry, x):
        s_prev, z_prev = carry
        qc, kc, vc, lgc = x                # [B,Hkv,G,c,d] [B,Hkv,c,d] ..
        cum = jnp.cumsum(lgc, axis=-1)     # [B,Hkv,c], <= 0
        with jax.named_scope("retention/chunk_intra"):
            sc = scale * jnp.einsum("bhgtd,bhid->bhgti", qc, kc)
            decay = jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf)
            w = jnp.square(sc) * jnp.exp(decay)[:, :, None]
            num = jnp.einsum("bhgti,bhid->bhgtd", w, vc)
            den = jnp.sum(w, axis=-1)
        with jax.named_scope("retention/phi"):
            # phi is the scan's largest array (c x D a head); it leaves
            # its fusion in the type the matmuls multiply in
            fq = (scale * scale * symmetric_square(qc)).astype(mm)
            fk = symmetric_square(kc).astype(mm)
        with jax.named_scope("retention/chunk_state"):
            since = jnp.exp(cum)[:, :, None]                  # [B,Hkv,1,c]
            num = num + since[..., None] * jnp.einsum(
                "bhgtD,bhvD->bhgtv", fq, s_prev, **acc)
            den = den + since * jnp.einsum("bhgtD,bhD->bhgt", fq,
                                           z_prev[:, :, 0], **acc)
            left = jnp.exp(cum[..., -1:] - cum)               # [B,Hkv,c]
            whole = jnp.exp(cum[..., -1])[..., None, None]
            s_new = whole * s_prev + jnp.einsum(
                "bhiv,bhiD->bhvD", (vc * left[..., None]).astype(mm), fk,
                **acc)
            z_new = whole * z_prev + jnp.einsum(
                "bhi,bhiD->bhD", left.astype(mm), fk, **acc)[:, :, None]
        return (s_new, z_new), num / (den[..., None] + eps)

    (state, norm), ys = jax.lax.scan(
        one, (state.astype(jnp.float32), norm.astype(jnp.float32)), xs)
    y = jnp.moveaxis(ys, 0, 3).reshape(b, hq, n * c, dv)[:, :, :t]
    return y.astype(v.dtype), state, norm


# -- the prefill from an empty state ------------------------------------------

PREFILL_BLOCK = 256     # query and key positions a grid step of the kernel
STATE_CHUNK = 512       # positions between two updates of the state


def _prefill_kernel(cq_ref, ck_ref, q_ref, k_ref, v_ref, y_ref, acc, den, *,
                    scale: float, eps: float, blk: int):
    """One (head, block of queries, block of keys) of the quadratic form:
    the weights of the block under the decay mask, their product with v and
    their sum, added up over the key blocks at or before the queries'."""
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        den[...] = jnp.zeros_like(den)

    @pl.when(kb <= qb)
    def _():
        s = scale * jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [blk, blk]
        rows = qb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kb * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        decay = jnp.where(cols <= rows, cq_ref[0] - ck_ref[0], -jnp.inf)
        w = s * s * jnp.exp(decay)
        acc[...] += jnp.dot(w.astype(v_ref.dtype), v_ref[0],
                            preferred_element_type=jnp.float32)
        den[...] += jnp.sum(w, axis=1, keepdims=True)

    @pl.when(kb == qb)
    def _():
        y_ref[0] = (acc[...] / (den[...] + eps)).astype(y_ref.dtype)


def prefill_kernel_refusal(q_shape) -> Optional[str]:
    """Why Mosaic cannot take the prefill kernel at these shapes, or
    None."""
    _, _, t, d = q_shape
    if d % 128:
        return "head_dim %d is not a multiple of 128 lanes" % d
    if t % min(t, PREFILL_BLOCK) or min(t, PREFILL_BLOCK) % 128:
        return ("%d positions are not whole blocks of %d (or one block of "
                "whole 128-lane rows)" % (t, PREFILL_BLOCK))
    return None


@functools.partial(jax.jit, static_argnames=("scale", "eps", "interpret"))
def _prefill_pallas(q, k, v, cum, scale: float, eps: float,
                    interpret: bool = False):
    """``y`` ``[B, Hq, T, dv]`` of the quadratic form from an empty state;
    ``cum`` ``[B, Hkv, T]`` is the running sum of the log-gates."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g = hq // hkv
    blk = min(t, PREFILL_BLOCK)
    n = t // blk
    kv = lambda h, i, j: (h // g, jnp.minimum(i, j), 0)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    y = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, eps=eps, blk=blk),
        grid=(b * hq, n, n),
        in_specs=[pl.BlockSpec((1, blk, 1), lambda h, i, j: (h // g, i, 0)),
                  pl.BlockSpec((1, 1, blk),
                               lambda h, i, j: (h // g, 0,
                                                jnp.minimum(i, j))),
                  pl.BlockSpec((1, blk, d), lambda h, i, j: (h, i, 0)),
                  pl.BlockSpec((1, blk, d), kv),
                  pl.BlockSpec((1, blk, dv), kv)],
        out_specs=pl.BlockSpec((1, blk, dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((blk, dv), jnp.float32),
                        pltpu.VMEM((blk, 1), jnp.float32)],
        interpret=interpret,
        name="power_retention_prefill",
        **kwargs,
    )(cum.reshape(b * hkv, t, 1), cum.reshape(b * hkv, 1, t),
      q.reshape(b * hq, t, d), k.reshape(b * hkv, t, d),
      v.reshape(b * hkv, t, dv))
    return y.reshape(b, hq, t, dv)


def _state_after(k, v, lg, chunk: int):
    """``(S, z)`` that the positions leave behind them, from an empty
    state: a scan over chunks of ``chunk`` positions that takes only the
    state's update of :func:`power_retention_chunked` (``phi(k)``, an
    eighth of the chunked form's ``phi``: no query is read)."""
    b, hkv, t, d = k.shape
    dv = v.shape[-1]
    c = min(t, chunk)
    pad = -t % c
    if pad:       # trailing identity steps: phi(0) = 0, log-gate 0
        k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                for x in (k, v))
        lg = jnp.pad(lg, ((0, 0), (0, 0), (0, pad)))
    n = (t + pad) // c
    mm = k.dtype
    acc = {"preferred_element_type": jnp.float32}
    split = lambda x: jnp.moveaxis(
        x.reshape(x.shape[:2] + (n, c) + x.shape[3:]), 2, 0)

    def one(carry, x):
        s_prev, z_prev = carry
        kc, vc, lgc = x
        cum = jnp.cumsum(lgc, axis=-1)
        with jax.named_scope("retention/phi"):
            fk = symmetric_square(kc).astype(mm)
        with jax.named_scope("retention/chunk_state"):
            left = jnp.exp(cum[..., -1:] - cum)
            whole = jnp.exp(cum[..., -1])[..., None, None]
            s_new = whole * s_prev + jnp.einsum(
                "bhiv,bhiD->bhvD",
                (vc.astype(jnp.float32) * left[..., None]).astype(mm), fk,
                **acc)
            z_new = whole * z_prev + jnp.einsum(
                "bhi,bhiD->bhD", left.astype(mm), fk, **acc)[:, :, None]
        return (s_new, z_new), None

    d_phi = phi_size(d)
    zero = (jnp.zeros((b, hkv, dv, d_phi), jnp.float32),
            jnp.zeros((b, hkv, 1, d_phi), jnp.float32))
    (s, z), _ = jax.lax.scan(one, zero, (split(k), split(v), split(lg)))
    return s, z


def power_retention_prefill(q, k, v, log_gate, keep=None,
                            route: str = "auto", scale: float = 1.0,
                            eps: float = EPS):
    """The prefill as a server runs it: FROM AN EMPTY STATE.  Same
    arguments and results as :func:`power_retention_chunked` without the
    state going in.  With nothing before the first position the outputs
    need no state at all: on a TPU they are the quadratic form itself in a
    Pallas kernel over (head, block of queries, block of keys at or before
    them) that never writes a score to memory, and the state is taken
    once, from ``phi(k)`` alone (:func:`_state_after`); the chunked form's
    ``phi(q)``, 737 KB a position a layer at 40 heads of 128, is never
    made.  Elsewhere, or where Mosaic cannot tile the shapes
    (:func:`prefill_kernel_refusal`), it IS the chunked form from zeros.
    ``route`` as in :func:`power_retention_step`."""
    from .flash_attention import _cached_backend

    b, hq, t, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    if route not in ("auto", "pallas", "composition"):
        raise InvalidArgumentError(
            "route must be 'auto', 'pallas' or 'composition', got %r"
            % (route,))
    refusal = prefill_kernel_refusal(q.shape)
    if route == "pallas" and refusal:
        raise InvalidArgumentError(
            "the power-retention prefill kernel cannot take %s: %s"
            % (tuple(q.shape), refusal))
    on_tpu = _cached_backend() == "tpu"
    if route == "composition" or (route == "auto"
                                  and (refusal or not on_tpu)):
        d_phi = phi_size(d)
        return power_retention_chunked(
            q, k, v, log_gate, jnp.zeros((b, hkv, dv, d_phi), jnp.float32),
            jnp.zeros((b, hkv, 1, d_phi), jnp.float32), keep, scale=scale,
            eps=eps)
    lg = log_gate.astype(jnp.float32)
    if keep is not None:
        live = keep[:, None, :]
        lg = jnp.where(live, lg, 0.0)
        k = jnp.where(live[..., None], k, jnp.zeros_like(k))
    with jax.named_scope("retention/chunk_intra"):
        y = _prefill_pallas(q, k, v, jnp.cumsum(lg, axis=-1), float(scale),
                            float(eps), interpret=not on_tpu)
    state, norm = _state_after(k, v, lg, STATE_CHUNK)
    return y, state, norm


# -- the decode step --------------------------------------------------------

def step_tile(d_phi: int, dv: int) -> Optional[int]:
    """Entries of ``D`` one grid step of the kernel takes: the largest
    divisor of ``D`` that is a multiple of 128 lanes and keeps a block of
    ``S`` within ``_VMEM_STEP_BYTES`` (the block is held four times: in and
    out, each double-buffered).  None where ``D`` has no such divisor."""
    return max((lanes for lanes in range(128, d_phi + 1, 128)
                if d_phi % lanes == 0
                and lanes * dv * 4 <= _VMEM_STEP_BYTES), default=None)


def step_kernel_refusal(state_shape) -> Optional[str]:
    """Why Mosaic cannot take the step kernel at these shapes, or None."""
    _, _, dv, d_phi = state_shape
    if dv % 8:
        return "value width %d is not a multiple of 8 sublanes" % dv
    if step_tile(d_phi, dv) is None:
        return ("phi size %d has no divisor of whole 128-lane rows that "
                "fits a block of S in VMEM" % d_phi)
    return None


def _step_kernel(g_ref, v_ref, fk_ref, fq_ref, s_ref, z_ref,
                 s_out, z_out, num_ref, den_ref, *, group: int):
    """One ``(row, K/V head, tile of D)``: the rank-one update of the
    tile, written back over what was read, and the tile's part of every
    query head's numerator and denominator, summed over the tiles."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    g = g_ref[0, 0]                                   # [1, 1]
    fk = fk_ref[0, 0]                                 # [1, T]
    s = g * s_ref[0, 0] + v_ref[0, 0] * fk            # [dv, T]
    z = g * z_ref[0, 0] + fk                          # [1, T]
    s_out[0, 0] = s
    z_out[0, 0] = z

    first = j == 0
    for a in range(group):
        fq = fq_ref[0, 0, a:a + 1, :]                 # [1, T]
        num = jnp.sum(s * fq, axis=1, keepdims=True)  # [dv, 1]
        den = jnp.sum(z * fq, axis=1, keepdims=True)  # [1, 1]
        num_ref[0, 0, a] = jnp.where(first, 0.0, num_ref[0, 0, a]) + num
        den_ref[0, 0, a] = jnp.where(first, 0.0, den_ref[0, 0, a]) + den


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(g, v, fk, fq, state, norm, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, dv, d_phi = state.shape
    group = fq.shape[2]
    tile = step_tile(d_phi, dv)
    grid = (b, hkv, d_phi // tile)
    row = lambda i, h, j: (i, h, 0, 0)
    along = lambda i, h, j: (i, h, 0, j)
    acc = lambda i, h, j: (i, h, 0, 0, 0)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=8 * _VMEM_STEP_BYTES + (16 << 20))
    return pl.pallas_call(
        functools.partial(_step_kernel, group=group),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, 1, 1), row),            # g
                  pl.BlockSpec((1, 1, dv, 1), row),           # v
                  pl.BlockSpec((1, 1, 1, tile), along),       # phi(k)
                  pl.BlockSpec((1, 1, group, tile), along),   # phi(q)
                  pl.BlockSpec((1, 1, dv, tile), along),      # S
                  pl.BlockSpec((1, 1, 1, tile), along)],      # z
        out_specs=[pl.BlockSpec((1, 1, dv, tile), along),
                   pl.BlockSpec((1, 1, 1, tile), along),
                   pl.BlockSpec((1, 1, group, dv, 1), acc),
                   pl.BlockSpec((1, 1, group, 1, 1), acc)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct(norm.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, group, dv, 1),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, group, 1, 1),
                                        jnp.float32)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="power_retention_step",
        **kwargs,
    )(g, v, fk, fq, state, norm)


def _step_composition(g, v, fk, fq, state, norm):
    """The kernel's arithmetic as XLA operations."""
    s = g * state + v * fk
    z = g * norm + fk
    num = jnp.einsum("bhgD,bhvD->bhgv", fq, s,
                     precision=jax.lax.Precision.HIGHEST)
    den = jnp.einsum("bhgD,bhD->bhg", fq, z[:, :, 0],
                     precision=jax.lax.Precision.HIGHEST)
    return s, z, num[..., None], den[..., None, None]


def _step_route(state_shape, route: str) -> str:
    from .flash_attention import _cached_backend

    if route not in ("auto", "pallas", "composition"):
        raise InvalidArgumentError(
            "route must be 'auto', 'pallas' or 'composition', got %r"
            % (route,))
    if route == "composition":
        return route
    refusal = step_kernel_refusal(state_shape)
    if route == "pallas" and refusal:
        raise InvalidArgumentError(
            "the power-retention step kernel cannot take state %s: %s"
            % (tuple(state_shape), refusal))
    if route == "pallas" or (_cached_backend() == "tpu" and not refusal):
        return "pallas"
    return "composition"


def power_retention_step(q, k, v, log_gate, state, norm, keep=None,
                         route: str = "auto", scale: float = 1.0,
                         eps: float = EPS):
    """The decode step: one position a row.  ``q`` ``[B, Hq, d]``, ``k``,
    ``v`` ``[B, Hkv, d]``, ``log_gate`` ``[B, Hkv]``; ``scale`` multiplies
    ``q . k`` before the square; ``state``
    ``[B, Hkv, dv, D]``, ``norm`` ``[B, Hkv, 1, D]`` float32; ``keep``
    ``[B]``: a row where it is false is an identity step and its output is
    of no use.  Returns ``(y [B, Hq, dv] in v's type, state, norm)``; under
    ``jit`` with the state donated the update is in place.

    ``route``: ``auto`` takes the Pallas kernel on a TPU where Mosaic can
    tile the shapes and the XLA composition elsewhere; ``pallas`` forces the
    kernel (under the interpreter off the TPU) or raises."""
    from .flash_attention import _cached_backend

    b, hq, _ = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    group = hq // hkv
    if state.dtype != jnp.float32 or norm.dtype != jnp.float32:
        raise InvalidArgumentError(
            "the retention state is float32 (got %s / %s): every later "
            "token reads what each step leaves in it"
            % (state.dtype, norm.dtype))
    with jax.named_scope("retention/phi"):
        fq = scale * scale * symmetric_square(
            q.reshape(b, hkv, group, -1))
        fk = symmetric_square(k)[:, :, None]                # [B,Hkv,1,D]
    g = jnp.exp(log_gate.astype(jnp.float32))
    if keep is not None:
        g = jnp.where(keep[:, None], g, 1.0)
        fk = jnp.where(keep[:, None, None, None], fk, 0.0)
    g = g[:, :, None, None]
    vcol = v.astype(jnp.float32)[..., None]                 # [B,Hkv,dv,1]
    taken = _step_route(state.shape, route)
    with jax.named_scope("retention/step"):
        if taken == "pallas":
            state, norm, num, den = _step_pallas(
                g, vcol, fk, fq, state, norm,
                interpret=_cached_backend() != "tpu")
        else:
            state, norm, num, den = _step_composition(
                g, vcol, fk, fq, state, norm)
        y = num[..., 0] / (den[..., 0] + eps)               # [B,Hkv,G,dv]
    return y.reshape(b, hq, dv).astype(v.dtype), state, norm


def state_bytes(hkv: int, d: int, dv: int) -> int:
    """Bytes of ``S`` and ``z`` of one row of one layer."""
    return 4 * hkv * phi_size(d) * (dv + 1)

