"""Checkpoint save/load.

Reference parity: ``python/paddle/framework/io.py:550`` (``paddle.save``:
nested state_dicts / arbitrary picklable objects / Layer+optimizer states)
and ``:766`` (``paddle.load``).  The on-disk format here is a directory-free
two-file pair like jit.save's: ``<path>`` (pickled structure with array
placeholders) — arrays hoisted into ``<path>.npz`` so checkpoints stream
instead of pickling gigabytes through Python.

Sharded design (SURVEY §5.4 dist_sharding_save parity): ``save`` accepts
globally-sharded ``jax.Array``s — each *process* writes only the shards it
addresses (``<path>.shard<K>.npz``) plus its own index fragment
(``<path>.index<K>.json``, chunk keys namespaced by process); ``load``
merges all fragments, reassembles, and raises if the chunks do not cover
every array completely.  On one host this degenerates to the plain pair.
This is the multi-host checkpoint layout NCCL-based paddle gets from
per-rank files.
"""
from __future__ import annotations

import json
import os
import pickle
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from ..core.errors import InvalidArgumentError
from .tensor import Parameter, Tensor

__all__ = ["save", "load"]

_ARRAYS_SUFFIX = ".npz"
_SHARD_SUFFIX = ".shard%d.npz"
_INDEX_SUFFIX = ".index.json"          # legacy single-process index
_INDEX_FRAG_SUFFIX = ".index%d.json"   # per-process index fragment

# dtypes np.savez can't round-trip (ml_dtypes: bfloat16, fp8 variants) are
# stored as their bit-equivalent uint view; the real dtype travels alongside.
_BITS_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # jax dependency, always present

        return np.dtype(getattr(ml_dtypes, name))


def _savable(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """Return (npz-safe array, real dtype name or '')."""
    # ml_dtypes register as void-kind scalar dtypes (names is None);
    # structured/void numpy arrays (names set) round-trip through savez as-is.
    if arr.dtype.kind == "V" and arr.dtype.names is None \
            and arr.dtype.itemsize in _BITS_UINT:
        return arr.view(_BITS_UINT[arr.dtype.itemsize]), arr.dtype.name
    return arr, ""


class _ArrayRef:
    """Pickled placeholder for an array hoisted to the npz sidecar."""

    __slots__ = ("key", "kind", "dtype")

    def __init__(self, key: str, kind: str, dtype: str = ""):
        self.key = key
        self.kind = kind  # "tensor" | "parameter" | "ndarray"
        self.dtype = dtype  # real dtype name when npz stores a uint view


def _is_fully_addressable(v: jax.Array) -> bool:
    return v.is_fully_addressable


def _hoist(obj, arrays: Dict[str, np.ndarray],
           sharded: List[Tuple[str, jax.Array]], prefix: str = "a"):
    """Replace arrays in a nested structure with _ArrayRef placeholders."""
    if isinstance(obj, Parameter):
        key = "%s%d" % (prefix, len(arrays) + len(sharded))
        arrays[key], dt = _savable(np.asarray(obj.value))
        return _ArrayRef(key, "parameter", dt)
    if isinstance(obj, Tensor):
        key = "%s%d" % (prefix, len(arrays) + len(sharded))
        arrays[key], dt = _savable(np.asarray(obj.value))
        return _ArrayRef(key, "tensor", dt)
    if isinstance(obj, jax.Array):
        key = "%s%d" % (prefix, len(arrays) + len(sharded))
        if not _is_fully_addressable(obj):
            sharded.append((key, obj))
            return _ArrayRef(key, "ndarray")
        arrays[key], dt = _savable(np.asarray(obj))
        return _ArrayRef(key, "ndarray", dt)
    if isinstance(obj, np.ndarray):
        key = "%s%d" % (prefix, len(arrays) + len(sharded))
        arrays[key], dt = _savable(obj)
        return _ArrayRef(key, "ndarray", dt)
    if isinstance(obj, dict):
        return {k: _hoist(v, arrays, sharded, prefix) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        seq = [_hoist(v, arrays, sharded, prefix) for v in obj]
        return seq if isinstance(obj, list) else tuple(seq)
    return obj


def _restore(obj, arrays, return_numpy: bool):
    if isinstance(obj, _ArrayRef):
        v = arrays[obj.key]
        real = getattr(obj, "dtype", "")
        if real:
            v = v.view(_np_dtype(real))
        if return_numpy:
            return v
        if obj.kind == "parameter":
            return Parameter(v)
        return Tensor(v, stop_gradient=True)
    if isinstance(obj, dict):
        return {k: _restore(v, arrays, return_numpy) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        seq = [_restore(v, arrays, return_numpy) for v in obj]
        return seq if isinstance(obj, list) else tuple(seq)
    return obj


def _boxes_cover(boxes, shape) -> bool:
    """True when the union of axis-aligned boxes covers the full shape.

    Fast path: deduplicated boxes (replicated shards write identical ones)
    that are pairwise disjoint cover iff their sizes sum to the total.  The
    irregular-overlap case falls back to an exact boolean mask.
    """
    total = int(np.prod(shape)) if shape else 1
    uniq = sorted(set(boxes))
    sizes = [int(np.prod([b - a for a, b in bx])) if bx else 1 for bx in uniq]
    disjoint = True
    for i in range(len(uniq)):
        for j in range(i + 1, len(uniq)):
            if all(a1 < b2 and a2 < b1 for (a1, b1), (a2, b2)
                   in zip(uniq[i], uniq[j])):
                disjoint = False
                break
        if not disjoint:
            break
    if disjoint:
        return sum(sizes) == total
    covered = np.zeros(shape, dtype=bool)
    for bx in uniq:
        covered[tuple(slice(a, b) for a, b in bx)] = True
    return bool(covered.all())


def save(obj: Any, path: str, protocol: int = 4, **configs) -> None:
    """``paddle.save`` parity (framework/io.py:550)."""
    if not isinstance(path, (str, os.PathLike)):
        raise InvalidArgumentError("save path must be a string, got %r" % (path,))
    path = os.fspath(path)
    if path.endswith("/"):
        raise InvalidArgumentError("save path %r is a directory" % path)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)

    arrays: Dict[str, np.ndarray] = {}
    sharded: List[Tuple[str, jax.Array]] = []
    skeleton = _hoist(obj, arrays, sharded)

    pidx = jax.process_index()
    if sharded:
        # Per-process shard files + per-process index fragments
        # (dist_sharding_save layout).  Chunk keys are namespaced by process
        # index so concurrent writers never collide; every process records
        # its own fragment and load() merges them and checks full coverage.
        index = {"arrays": {}, "nprocesses": jax.process_count(),
                 "process": pidx}
        shard_arrays: Dict[str, np.ndarray] = {}
        for key, arr in sharded:
            chunks = []
            for i, s in enumerate(arr.addressable_shards):
                ck = "%s/p%d/chunk%d" % (key, pidx, i)
                shard_arrays[ck], _ = _savable(np.asarray(s.data))
                chunks.append({
                    "key": ck,
                    "index": [[sl.start or 0, sl.stop if sl.stop is not None
                               else dim] for sl, dim in
                              zip(s.index, arr.shape)],
                })
            index["arrays"][key] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "chunks": chunks,
            }
        np.savez(path + _SHARD_SUFFIX % pidx, **shard_arrays)
        with open(path + _INDEX_FRAG_SUFFIX % pidx, "w") as f:
            json.dump(index, f)
    if pidx == 0:
        # Drop stale sidecars from a previous save at this path so load()
        # never merges old fragments into the new checkpoint: the legacy
        # single index, and fragments/shards beyond the current world size
        # (files 0..nproc-1 are overwritten by their owning processes).
        nproc = jax.process_count() if sharded else 0
        for stale in (path + _INDEX_SUFFIX,):
            if os.path.exists(stale):
                os.remove(stale)
        k = nproc
        while os.path.exists(path + _INDEX_FRAG_SUFFIX % k) \
                or os.path.exists(path + _SHARD_SUFFIX % k):
            for stale in (path + _INDEX_FRAG_SUFFIX % k,
                          path + _SHARD_SUFFIX % k):
                if os.path.exists(stale):
                    os.remove(stale)
            k += 1
        np.savez(path + _ARRAYS_SUFFIX, **arrays)
        with open(path, "wb") as f:
            pickle.dump(skeleton, f, protocol=protocol)


def load(path: str, return_numpy: bool = False, **configs) -> Any:
    """``paddle.load`` parity (framework/io.py:766)."""
    path = os.fspath(path)
    if not os.path.exists(path):
        raise InvalidArgumentError("checkpoint %r not found" % path)
    with open(path, "rb") as f:
        skeleton = pickle.load(f)
    arrays: Dict[str, np.ndarray] = {}
    if os.path.exists(path + _ARRAYS_SUFFIX):
        with np.load(path + _ARRAYS_SUFFIX, allow_pickle=False) as z:
            arrays.update({k: z[k] for k in z.files})
    # Merge index fragments (new layout) and/or the legacy single index.
    merged: Dict[str, dict] = {}
    frags = []
    if os.path.exists(path + _INDEX_SUFFIX):
        frags.append(path + _INDEX_SUFFIX)
    k = 0
    while os.path.exists(path + _INDEX_FRAG_SUFFIX % k):
        frags.append(path + _INDEX_FRAG_SUFFIX % k)
        k += 1
    expect_nproc = None
    n_frag_files = 0
    for fp in frags:
        with open(fp) as f:
            index = json.load(f)
        if "process" in index:  # fragment format (legacy index lacks it)
            n_frag_files += 1
            if expect_nproc is None:
                expect_nproc = index.get("nprocesses")
        for key, meta in index["arrays"].items():
            ent = merged.setdefault(
                key, {"shape": meta["shape"], "dtype": meta["dtype"],
                      "chunks": []})
            if ent["shape"] != meta["shape"] or ent["dtype"] != meta["dtype"]:
                raise InvalidArgumentError(
                    "checkpoint index fragments disagree on %r: shape/dtype "
                    "%r/%r vs %r/%r" % (key, ent["shape"], ent["dtype"],
                                        meta["shape"], meta["dtype"]))
            ent["chunks"].extend(meta["chunks"])
    if expect_nproc is not None and n_frag_files < expect_nproc:
        missing = [i for i in range(expect_nproc)
                   if not os.path.exists(path + _INDEX_FRAG_SUFFIX % i)]
        raise InvalidArgumentError(
            "checkpoint %r was written by %d processes but only %d index "
            "fragment(s) are present (missing: %r)" %
            (path, expect_nproc, n_frag_files, missing))
    if merged:
        shard_data: Dict[str, np.ndarray] = {}
        k = 0
        while os.path.exists(path + _SHARD_SUFFIX % k):
            with np.load(path + _SHARD_SUFFIX % k, allow_pickle=False) as z:
                shard_data.update({n: z[n] for n in z.files})
            k += 1
        for key, meta in merged.items():
            dt = _np_dtype(meta["dtype"])
            full = np.zeros(meta["shape"], dtype=dt)
            boxes = []
            for chunk in meta["chunks"]:
                if chunk["key"] not in shard_data:
                    raise InvalidArgumentError(
                        "checkpoint shard chunk %r missing (found %d shard "
                        "files)" % (chunk["key"], k))
                sl = tuple(slice(a, b) for a, b in chunk["index"])
                full[sl] = shard_data[chunk["key"]].view(dt).reshape(
                    full[sl].shape)
                boxes.append(tuple((a, b) for a, b in chunk["index"]))
            if not _boxes_cover(boxes, meta["shape"]):
                raise InvalidArgumentError(
                    "checkpoint %r: shard chunks do not cover all of %r "
                    "(shape %r) — missing per-process shard files?" %
                    (path, key, meta["shape"]))
            arrays[key] = full
    return _restore(skeleton, arrays, return_numpy)
