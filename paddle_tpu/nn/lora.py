"""Batched multi-LoRA serving: a stacked per-adapter low-rank delta
resolved per row INSIDE the shared compiled step (docs/DESIGN.md §5q).

One base model, many fine-tunes, one compile budget.  ``attach_lora``
creates a ``[n_adapters, d_in, r]`` / ``[n_adapters, r, d_out]``
zero-init bank beside each target projection's base weight; the forward
then adds ``(x @ A[ids]) @ B[ids]`` where ``ids`` is the batch's traced
per-row adapter-id vector — ONE ``take`` gather plus two batched
einsums XLA fuses into the projection matmuls, never a per-request
dispatch.

Invariants the rest of the stack leans on:

- **Adapter id 0 is the identity.**  Row 0 of every bank is all-zero
  and ``load_adapter`` refuses to write it, so the delta for id-0 rows
  is exactly zero and their tokens are bit-identical to the base model
  — a mixed batch needs no branch to keep base requests exact.
- **The bank rides ``param_vals``.**  ``attach_lora`` MUST run before
  any ``DecodeSession``/``GenerationPool``/``ServingEngine`` is
  constructed over the model: the jit state binding snapshots
  ``named_parameters()`` at construction, and only snapshot parameters
  flow into the traced bodies as arguments (anything else would be
  baked into the executable as a constant — the retrace hazard the
  linter flags).
- **Hot-swap, never recompile.**  ``load_adapter``/``unload_adapter``
  rewrite bank ROWS in place (shapes unchanged) exactly like
  ``refresh_weights`` weight pushes; a serving pool/engine picks the
  new rows up on its next tick after ``refresh_weights()`` with zero
  new compiles and an unchanged ``cost_version()``.
- **The id vector is ambient, the VALUES are data.**  ``adapter_ids``
  is a context manager the traced session/pool bodies wrap around the
  model forward; what it holds is a TRACED per-row vector argument of
  the step, so which adapter a slot uses is data — only the bank
  GEOMETRY (n_adapters, rank — the shapes) is compiled in, and that is
  what the pool's config fingerprint carries.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError

__all__ = ["attach_lora", "load_adapter", "unload_adapter",
           "adapter_ids", "current_adapter_ids", "lora_linears",
           "lora_config", "random_adapter", "adapter_bank_bytes",
           "DEFAULT_TARGETS"]

#: attention projections of ``nn.MultiHeadAttention`` — the classic
#: LoRA target set; MLP linears can be added via ``targets=``.
DEFAULT_TARGETS = ("q_proj", "k_proj", "v_proj", "out_proj")

_ADAPTER_IDS = contextvars.ContextVar("lora_adapter_ids", default=None)


@contextlib.contextmanager
def adapter_ids(ids):
    """Make ``ids`` (a traced [B] int vector, or None for base-only)
    the ambient per-row adapter selection for every bank-attached
    Linear forward underneath — the decode bodies wrap their model call
    in this, so the ids stay an ordinary traced argument of the step."""
    token = _ADAPTER_IDS.set(ids)
    try:
        yield
    finally:
        _ADAPTER_IDS.reset(token)


def current_adapter_ids():
    """The ambient adapter-id vector, or None outside a decode body."""
    return _ADAPTER_IDS.get()


def apply_delta(out, x, lora_a, lora_b, ids):
    """``out + (x @ A[ids]) @ B[ids]`` — the gathered batched low-rank
    delta, fused into the projection by XLA.  ``x`` is ``[B, ..., d_in]``
    with leading batch matching ``ids`` [B]; id-0 rows add an exact
    zero (the bank's reserved identity row)."""
    xv = getattr(x, "value", x)
    av = getattr(lora_a, "value", lora_a)
    bv = getattr(lora_b, "value", lora_b)
    idv = jnp.asarray(getattr(ids, "value", ids), jnp.int32)
    a = jnp.take(av, idv, axis=0)                 # [B, d_in, r]
    b = jnp.take(bv, idv, axis=0)                 # [B, r, d_out]
    mid = jnp.einsum("b...i,bir->b...r", xv, a)
    delta = jnp.einsum("b...r,bro->b...o", mid, b)
    from ..framework.tensor import Tensor

    return out + Tensor(delta.astype(getattr(out, "value", out).dtype),
                        stop_gradient=True)


def attach_lora(model, n_adapters: int, rank: int,
                targets: Tuple[str, ...] = DEFAULT_TARGETS):
    """Create the stacked zero-init adapter bank on every target Linear
    under ``model`` (in place; returns the model).

    Must run BEFORE any session/pool/engine construction over the model
    — the bank has to be in the binding's parameter snapshot to ride
    ``param_vals`` into the traced step.  ``n_adapters`` counts row 0,
    the reserved all-zero identity, so serving N fine-tunes needs
    ``n_adapters >= N + 1``."""
    from .initializer import Constant

    if int(n_adapters) < 2:
        raise InvalidArgumentError(
            "n_adapters must be >= 2 (row 0 is the reserved identity "
            "adapter — the base model), got %r" % (n_adapters,))
    if int(rank) < 1:
        raise InvalidArgumentError(
            "rank must be >= 1, got %r" % (rank,))
    n, r = int(n_adapters), int(rank)
    count = 0
    for _, sub in model.named_sublayers(include_self=True):
        for tname in targets:
            lin = getattr(sub, tname, None)
            if lin is None or getattr(lin, "weight", None) is None \
                    or not hasattr(lin, "create_parameter"):
                continue
            if lin._parameters.get("lora_a") is not None:
                raise InvalidArgumentError(
                    "a LoRA bank is already attached to %r — attach_lora "
                    "runs once per model; use load_adapter/unload_adapter "
                    "to change adapter contents" % (tname,))
            d_in, d_out = (int(lin.weight.shape[0]),
                           int(lin.weight.shape[1]))
            lin.lora_a = lin.create_parameter(
                [n, d_in, r], default_initializer=Constant(0.0))
            lin.lora_b = lin.create_parameter(
                [n, r, d_out], default_initializer=Constant(0.0))
            count += 1
    if count == 0:
        raise InvalidArgumentError(
            "attach_lora found no target Linear layers under %s "
            "(targets=%r): the model needs attention projections named "
            "like nn.MultiHeadAttention's, or pass targets= explicitly"
            % (type(model).__name__, targets))
    return model


def lora_linears(model) -> List[Tuple[str, object]]:
    """``[(qualname, Linear)]`` of every bank-attached Linear under
    ``model``, in ``named_sublayers`` order — the stable key set of an
    adapter's weight dict."""
    out = []
    for name, sub in model.named_sublayers(include_self=True):
        if getattr(sub, "_parameters", None) and \
                sub._parameters.get("lora_a") is not None:
            out.append((name, sub))
    return out


def lora_config(model) -> Optional[Tuple[int, int]]:
    """``(n_adapters, rank)`` of the attached bank, or None when the
    model has no bank — the GEOMETRY the pool's config fingerprint
    carries (shapes are compiled; contents are hot-swappable data)."""
    for _, lin in lora_linears(model):
        n, _, r = lin._parameters["lora_a"].shape
        return int(n), int(r)
    return None


def _check_idx(model, idx: int, verb: str) -> int:
    cfg = lora_config(model)
    if cfg is None:
        raise InvalidArgumentError(
            "no LoRA bank attached: call attach_lora(model, n_adapters, "
            "rank) before %s" % (verb,))
    n, _ = cfg
    idx = int(idx)
    if not 1 <= idx < n:
        raise InvalidArgumentError(
            "adapter id must be in [1, n_adapters=%d) — id 0 is the "
            "reserved identity row (the base model) and cannot be "
            "%sed; got %d" % (n, verb.split("_")[0], idx))
    return idx


def load_adapter(model, idx: int, weights: Dict[str, tuple]) -> None:
    """Write one adapter's ``(A [d_in, r], B [r, d_out])`` pairs into
    bank row ``idx`` in place — a row-granular ``refresh_weights``-style
    hot swap: shapes are unchanged, so no executable ever recompiles;
    serving callers must follow with ``refresh_weights()`` so the pool's
    cached state vector picks the new rows up.

    ``weights`` is keyed by the qualnames :func:`lora_linears` yields
    (missing or extra keys are typed errors — a silently half-loaded
    adapter would serve a franken-model)."""
    idx = _check_idx(model, idx, "load_adapter")
    pairs = lora_linears(model)
    names = {name for name, _ in pairs}
    extra = set(weights) - names
    if extra:
        raise InvalidArgumentError(
            "load_adapter got weights for unknown projections %s; the "
            "attached bank covers %s" % (sorted(extra), sorted(names)))
    for name, lin in pairs:
        if name not in weights:
            raise InvalidArgumentError(
                "load_adapter weights missing projection %r (the bank "
                "covers %s): a partially-loaded adapter would serve a "
                "mix of fine-tune and base rows" % (name, sorted(names)))
        a_new, b_new = weights[name]
        pa, pb = lin._parameters["lora_a"], lin._parameters["lora_b"]
        a_new = jnp.asarray(np.asarray(a_new), pa._value.dtype)
        b_new = jnp.asarray(np.asarray(b_new), pb._value.dtype)
        if a_new.shape != pa._value.shape[1:] or \
                b_new.shape != pb._value.shape[1:]:
            raise InvalidArgumentError(
                "adapter weights for %r have shapes A%s/B%s; the bank "
                "row needs A%s/B%s" % (name, tuple(a_new.shape),
                                       tuple(b_new.shape),
                                       tuple(pa._value.shape[1:]),
                                       tuple(pb._value.shape[1:])))
        pa._value = pa._value.at[idx].set(a_new)
        pb._value = pb._value.at[idx].set(b_new)


def unload_adapter(model, idx: int) -> None:
    """Zero bank row ``idx`` back to the identity — the row is free for
    the next ``load_adapter``; in-flight requests pinned to it would
    silently fall back to the base model, so callers drain first."""
    idx = _check_idx(model, idx, "unload_adapter")
    for _, lin in lora_linears(model):
        pa, pb = lin._parameters["lora_a"], lin._parameters["lora_b"]
        pa._value = pa._value.at[idx].set(jnp.zeros_like(pa._value[idx]))
        pb._value = pb._value.at[idx].set(jnp.zeros_like(pb._value[idx]))


def random_adapter(model, seed: int, scale: float = 0.02) \
        -> Dict[str, tuple]:
    """A deterministic random adapter weight dict for the attached bank
    (tests and examples) — keyed like :func:`load_adapter` expects."""
    cfg = lora_config(model)
    if cfg is None:
        raise InvalidArgumentError(
            "no LoRA bank attached: call attach_lora before "
            "random_adapter")
    rng = np.random.RandomState(int(seed))
    out = {}
    for name, lin in lora_linears(model):
        _, d_in, r = lin._parameters["lora_a"].shape
        _, _, d_out = lin._parameters["lora_b"].shape
        out[name] = (
            rng.normal(0.0, scale, (int(d_in), int(r))).astype(np.float32),
            rng.normal(0.0, scale, (int(r), int(d_out))).astype(
                np.float32))
    return out


def adapter_bank_bytes(model) -> int:
    """Total HBM bytes of the attached adapter bank (all rows, both
    factors) — the weight memory N fine-tunes cost beside N dedicated
    engines' full weight copies."""
    total = 0
    for _, lin in lora_linears(model):
        for pname in ("lora_a", "lora_b"):
            v = lin._parameters[pname]._value
            total += int(np.prod(v.shape)) * v.dtype.itemsize
    return total
