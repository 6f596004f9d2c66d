"""The selective scan of a Mamba-1 layer: a diagonal recurrence over a state
``s [N, C]`` a row (``N`` states a channel, ``C`` channels), whose decay is
chosen by the input,

    s[t] = exp(dt[t] (x) A) * s[t-1] + (dt[t] * c[t]) (x) B[t]
    y[t] = s[t] . C[t] + D * c[t]

with ``dt [C]`` (a step size a channel, >= 0), ``c [C]`` (the convolved
input), ``B``, ``C`` ``[N]`` and the parameters ``A [N, C]`` (< 0) and
``D [C]``.  ``A`` differs for every (channel, state) pair, so a chunk of
positions has no matmul form (``ops/power_retention.py``'s chunk has one gate
a head); what there is to choose is where the state lives.

The state is held with the CHANNELS INNERMOST, ``[rows, N, C]`` float32:
``N`` is 16, and the chip tiles the last two axes by (8, 128), so 16 on the
lanes would be padded eightfold in memory and in every load.

Three forms that agree to rounding:

- :func:`selective_scan_reference`: a ``lax.scan`` over the positions.
  What the others are tested against; it runs anywhere.
- :func:`selective_scan_step`: one position a row, the decode step.  The
  state goes in and comes out through one aliased buffer (a Pallas kernel
  over rows; the XLA composition where :func:`step_kernel_refusal` speaks or
  off the TPU).  Memory-bound: a row's state is read and written once.
- :func:`selective_scan_prefill`: many positions a row from a given state.
  A Pallas kernel over (row, tile of channels, block of positions) that
  keeps the tile's state in VMEM across the blocks and writes only ``y`` and
  the last state: no ``[T, N, C]`` array exists (at 1,024 positions and
  5,120 channels that array is 336 MB).  Off the TPU, and where
  :func:`prefill_kernel_refusal` speaks, the ``lax.scan``.

An identity step needs no select: ``dt = 0`` gives ``exp(0) = 1`` and adds
``0 * c * B``, so the state comes through to the bit.  The callers zero
``dt`` on a padded bucket's tail and on a pool's free rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.errors import InvalidArgumentError

__all__ = ["selective_scan_reference", "selective_scan_step",
           "selective_scan_prefill", "step_kernel_refusal",
           "prefill_kernel_refusal", "SCAN_BLOCK", "CHANNEL_TILE"]

SCAN_BLOCK = 128        # positions a grid step of the prefill kernel
CHANNEL_TILE = 1024     # channels a grid step of the prefill kernel, at most
_GROUP = 16             # positions the kernel's inner loop takes at once
                        # (a bfloat16 tile's 16 sublanes)
_STEP_VMEM = 8 << 20    # what the step kernel's state blocks may take


def _check(state, a):
    if state.dtype != jnp.float32:
        raise InvalidArgumentError(
            "the scan's state is float32 (got %s): every later token reads "
            "what each step leaves in it" % (state.dtype,))
    if state.shape[1:] != a.shape:
        raise InvalidArgumentError(
            "state %s does not hold A %s a row (channels innermost)"
            % (tuple(state.shape), tuple(a.shape)))


def _route(route: str, refusal: Optional[str], what: str) -> str:
    from .flash_attention import _cached_backend

    if route not in ("auto", "pallas", "composition"):
        raise InvalidArgumentError(
            "route must be 'auto', 'pallas' or 'composition', got %r"
            % (route,))
    if route == "composition":
        return route
    if route == "pallas" and refusal:
        raise InvalidArgumentError("the selective-scan %s kernel: %s"
                                   % (what, refusal))
    if route == "pallas" or (_cached_backend() == "tpu" and not refusal):
        return "pallas"
    return "composition"


def _interpret() -> bool:
    from .flash_attention import _cached_backend

    return _cached_backend() != "tpu"


# -- the sequential form ------------------------------------------------------

def selective_scan_reference(dt, c, b, cm, a, d, state):
    """``dt``, ``c`` ``[R, T, C]``; ``b``, ``cm`` ``[R, T, N]``; ``a``
    ``[N, C]``; ``d`` ``[C]``; ``state`` ``[R, N, C]`` float32.  Returns
    ``(y [R, T, C] float32, state)``: one position after the other."""
    _check(state, a)
    f32 = lambda x: x.astype(jnp.float32)

    def one(s, x):
        dt_t, c_t, b_t, cm_t = x
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * c_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * cm_t[:, :, None], axis=1) + d * c_t

    xs = tuple(jnp.moveaxis(f32(x), 1, 0) for x in (dt, c, b, cm))
    state, y = jax.lax.scan(one, state, xs)
    return jnp.moveaxis(y, 0, 1), state


# -- the decode step ----------------------------------------------------------

def _step_rows(rows: int, n: int, channels: int) -> int:
    """Rows a grid step of the step kernel takes: their state blocks are
    held four times (in and out, each double-buffered)."""
    return max(r for r in (8, 4, 2, 1)
               if rows % r == 0 and (r == 1
                                     or 16 * r * n * channels <= _STEP_VMEM))


def step_kernel_refusal(state_shape) -> Optional[str]:
    """Why Mosaic cannot take the step kernel at these shapes, or None."""
    _, n, channels = state_shape
    if n % 8:
        return "%d states a channel are not whole sublanes of 8" % n
    if channels % 128:
        return "%d channels are not whole rows of 128 lanes" % channels
    if 16 * n * channels > 4 * _STEP_VMEM:
        return ("one row's state of %d x %d floats does not fit VMEM four "
                "times" % (n, channels))
    return None


def _step_kernel(dt_ref, c_ref, b_ref, cm_ref, a_ref, d_ref, s_ref,
                 y_ref, s_out, *, rows: int):
    a = a_ref[...]                                        # [N, C]
    d = d_ref[...]                                        # [1, C]
    for r in range(rows):
        dt = dt_ref[r]                                    # [1, C]
        c = c_ref[r].astype(jnp.float32)                  # [1, C]
        s = jnp.exp(dt * a) * s_ref[r] + (dt * c) * b_ref[r]      # [N, C]
        s_out[r] = s
        y_ref[r] = jnp.sum(s * cm_ref[r], axis=0, keepdims=True) + d * c


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(dt, c, b, cm, a, d, state, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows_all, n, channels = state.shape
    rows = _step_rows(rows_all, n, channels)
    row = lambda i: (i, 0, 0)
    whole = lambda i: (0, 0)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * _STEP_VMEM + (16 << 20))
    vec = pl.BlockSpec((rows, 1, channels), row)
    col = pl.BlockSpec((rows, n, 1), row)
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows),
        grid=(rows_all // rows,),
        in_specs=[vec, vec, col, col,
                  pl.BlockSpec((n, channels), whole),
                  pl.BlockSpec((1, channels), whole),
                  pl.BlockSpec((rows, n, channels), row)],
        out_specs=[vec, pl.BlockSpec((rows, n, channels), row)],
        out_shape=[jax.ShapeDtypeStruct((rows_all, 1, channels),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="selective_scan_step",
        **kwargs,
    )(dt[:, None, :], c[:, None, :], b[:, :, None], cm[:, :, None], a,
      d[None, :], state)
    return y[:, 0], state


def _step_composition(dt, c, b, cm, a, d, state):
    c = c.astype(jnp.float32)
    s = jnp.exp(dt[:, None, :] * a) * state \
        + (dt * c)[:, None, :] * b[:, :, None]
    return jnp.sum(s * cm[:, :, None], axis=1) + d * c, s


def selective_scan_step(dt, c, b, cm, a, d, state, route: str = "auto"):
    """One position a row.  ``dt`` ``[R, C]`` float32 (0 on a row that must
    not move), ``c`` ``[R, C]``, ``b``, ``cm`` ``[R, N]`` float32, ``a``
    ``[N, C]``, ``d`` ``[C]`` float32, ``state`` ``[R, N, C]`` float32.
    Returns ``(y [R, C] float32, state)``; under ``jit`` with the state
    donated the update is in place.

    ``route``: ``auto`` takes the kernel on a TPU where Mosaic can tile the
    shapes and the composition elsewhere; ``pallas`` forces the kernel
    (under the interpreter off the TPU) or raises."""
    _check(state, a)
    taken = _route(route, step_kernel_refusal(state.shape), "step")
    if taken == "pallas":
        return _step_pallas(dt, c, b, cm, a, d, state,
                            interpret=_interpret())
    return _step_composition(dt, c, b, cm, a, d, state)


# -- the prefill ----------------------------------------------------------------

def channel_tile(channels: int) -> Optional[int]:
    """Channels a grid step of the prefill kernel takes: the largest divisor
    of ``channels`` in whole 128-lane rows up to ``CHANNEL_TILE`` (a tile's
    state, ``[N, tile]`` float32, stays in registers and VMEM for a whole
    block of positions)."""
    return max((t for t in range(128, min(channels, CHANNEL_TILE) + 1, 128)
                if channels % t == 0), default=None)


def prefill_kernel_refusal(length: int, state_shape) -> Optional[str]:
    """Why Mosaic cannot take the prefill kernel at these shapes, or None."""
    _, n, channels = state_shape
    if n % 8:
        return "%d states a channel are not whole sublanes of 8" % n
    if channel_tile(channels) is None:
        return "%d channels have no tile of whole 128-lane rows" % channels
    if length % _GROUP:
        return ("%d positions are not whole groups of %d"
                % (length, _GROUP))
    return None


def scan_block(length: int) -> int:
    """Positions a grid step takes: ``SCAN_BLOCK``, or all of a shorter or
    ragged chunk."""
    return SCAN_BLOCK if length % SCAN_BLOCK == 0 else length


def _prefill_kernel(dt_ref, c_ref, b_ref, cm_ref, a_ref, d_ref, s0_ref,
                    y_ref, s_out, s_acc, *, block: int):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_acc[...] = s0_ref[0]

    a = a_ref[...]                                        # [N, tile]
    d = d_ref[...]                                        # [1, tile]

    def group(g, s):
        at = pl.multiple_of(g * _GROUP, _GROUP)
        # a group of c at once: a packed type's single row cannot be
        # loaded at a position known only at run time
        cs = c_ref[0, pl.ds(at, _GROUP), :].astype(jnp.float32)
        for i in range(_GROUP):
            dt = dt_ref[0, pl.ds(at + i, 1), :]           # [1, tile]
            c = cs[i:i + 1]
            s = jnp.exp(dt * a) * s + (dt * c) * b_ref[0, at + i]
            y_ref[0, pl.ds(at + i, 1), :] = jnp.sum(
                s * cm_ref[0, at + i], axis=0, keepdims=True) + d * c
        return s

    s = jax.lax.fori_loop(0, block // _GROUP, group, s_acc[...])
    s_acc[...] = s

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        s_out[0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _prefill_pallas(dt, c, b, cm, a, d, state, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, length, channels = dt.shape
    n = a.shape[0]
    tile = channel_tile(channels)
    block = scan_block(length)
    grid = (rows, channels // tile, length // block)
    along = lambda r, k, j: (r, j, k)
    cols = lambda r, k, j: (r, j, 0, 0)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((1, block, tile), along),          # dt
                  pl.BlockSpec((1, block, tile), along),          # c
                  pl.BlockSpec((1, block, n, 1), cols),           # B
                  pl.BlockSpec((1, block, n, 1), cols),           # C
                  pl.BlockSpec((n, tile), lambda r, k, j: (0, k)),
                  pl.BlockSpec((1, tile), lambda r, k, j: (0, k)),
                  pl.BlockSpec((1, n, tile), lambda r, k, j: (r, 0, k))],
        out_specs=[pl.BlockSpec((1, block, tile), along),
                   pl.BlockSpec((1, n, tile), lambda r, k, j: (r, 0, k))],
        out_shape=[jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        interpret=interpret,
        name="selective_scan_prefill",
        **kwargs,
    )(dt, c, b[..., None], cm[..., None], a, d[None, :], state)


def selective_scan_prefill(dt, c, b, cm, a, d, state, route: str = "auto"):
    """Many positions a row, from ``state``.  ``dt`` ``[R, T, C]`` float32
    (0 at a position that must not move the state), ``c`` ``[R, T, C]``,
    ``b``, ``cm`` ``[R, T, N]`` float32, ``a`` ``[N, C]``, ``d`` ``[C]``
    float32, ``state`` ``[R, N, C]`` float32.  Returns ``(y [R, T, C]
    float32, state)``: the state after the last position."""
    _check(state, a)
    taken = _route(route, prefill_kernel_refusal(dt.shape[1], state.shape),
                   "prefill")
    if taken == "pallas":
        return _prefill_pallas(dt, c, b, cm, a, d, state,
                               interpret=_interpret())
    return selective_scan_reference(dt, c, b, cm, a, d, state)
