"""Collective communication API.

Reference parity: ``python/paddle/distributed/collective.py`` —
``Group``/``new_group`` (:78/:208), ``broadcast:332``, ``all_reduce:415``,
``reduce:496``, ``all_gather:584``, ``scatter:678``, ``alltoall:1456``,
``send:1515``/``recv:1578``, ``barrier:275`` — and the C++ collective ops they
lower to (``operators/collective/c_allreduce_op.h`` etc.).

TPU-native design (SURVEY §5.8): there are no rings, comm streams, or id
rendezvous.  A ``Group`` names a mesh axis of a ``jax.sharding.Mesh``; XLA
lowers ``lax.psum``/``all_gather``/``ppermute``/``all_to_all`` over that axis
to ICI/DCN collectives and schedules them (the ``c_sync_*`` stream-fence ops
dissolve).  Every collective here is dual-mode:

- **traced** (inside ``shard_map``/``pjit`` where the group's axis name is
  bound): operates on the per-device shard, exactly the reference's per-rank
  view.  This is the path TP/DP/SP layers use.
- **eager** (single-controller): operates on the *global* stacked view — axis
  0 of the input is the rank axis (shape ``[group_size, ...]``), the result is
  what every rank would hold.  Implemented by wrapping the traced form in
  ``shard_map`` over the group's mesh so the same XLA collective runs on real
  devices.  This replaces the reference's one-process-per-GPU eager mode,
  which cannot exist under a single controller.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.errors import InvalidArgumentError
from ..framework.tensor import Tensor



def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off (collectives
    intentionally change replication across the mapped axis).

    ``axis_names`` requests PARTIAL-manual mode: only those axes are
    manual inside the body, the rest stay GSPMD-managed."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
        axis_names=frozenset() if axis_names is None
        else frozenset(axis_names))


# the (static) size of a bound mapped axis; raises the axis-binding
# error when the name is unbound (``_axis_bound`` relies on that)
axis_size = lax.axis_size

__all__ = [
    "axis_size", "shard_map",
    "ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
    "is_initialized", "init_parallel_env", "get_rank", "get_world_size",
    "broadcast", "all_reduce", "reduce", "all_gather", "scatter", "alltoall",
    "all_to_all", "send", "recv", "isend", "irecv", "barrier", "wait",
    "reduce_scatter", "stream",
]


class ReduceOp:
    """collective.py:54 parity."""

    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communicator = a named axis of a device mesh (collective.py:78).

    ``ranks`` are global device indices (parity bookkeeping); ``mesh`` +
    ``axis_name`` are what collectives actually use.
    """

    _next_id = 0

    def __init__(
        self,
        ranks: Sequence[int],
        mesh: Mesh,
        axis_name: str,
        gid: Optional[int] = None,
    ):
        self.ranks = list(ranks)
        self.mesh = mesh
        self.axis_name = axis_name
        if gid is None:
            gid = Group._next_id
        Group._next_id = max(Group._next_id, gid) + 1
        self.id = gid

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    world_size = nranks

    @property
    def rank(self) -> int:
        # single-controller: the controller "is" rank 0 of every group
        return 0

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def is_member(self) -> bool:
        return True

    def __repr__(self):
        return "Group(id=%d, axis=%r, nranks=%d, ranks=%s)" % (
            self.id, self.axis_name, self.nranks, self.ranks)


# -- global state (collective.py _group_map analog) -------------------------
_group_map: dict = {}
_default_group: Optional[Group] = None


def _build_world_group() -> Group:
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("dp",))
    return Group(ranks=list(range(len(devices))), mesh=mesh, axis_name="dp", gid=0)


def _bootstrap_multihost() -> None:
    """Rendezvous via ``jax.distributed.initialize`` from PADDLE_TRAINER_* env.

    Reference parity: ``fleet/launch.py`` sets PADDLE_TRAINER_ID /
    PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS and ``parallel.py:49``
    rendezvouses over a TCP store + NCCL id broadcast.  TPU-native: the same
    env (synthesized by ``paddle_tpu.distributed.launch``) feeds JAX's
    coordination service — coordinator is rank 0's endpoint (PADDLE_MASTER).

    No-op when the env says single-process, or when the JAX backend/runtime
    is already initialized (e.g. the TPU runtime rendezvoused at import).
    """
    import os

    nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or "1")
    if nranks <= 1:
        return
    if jax.distributed.is_initialized():
        return  # already rendezvoused (runtime or a prior call)
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    coordinator = os.environ.get("PADDLE_MASTER") or \
        os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")[0]
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # cross-process CPU collectives need the gloo implementation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=nranks, process_id=rank)


def init_parallel_env() -> "Group":
    """``paddle.distributed.init_parallel_env`` parity (parallel.py:49).

    Reference: rendezvous via TCP store + NCCL id broadcast.  TPU-native:
    ``jax.distributed.initialize`` from the launcher's PADDLE_TRAINER_* env
    (multi-host controllers), then build the world mesh over global devices.
    """
    global _default_group
    if _default_group is None:
        _bootstrap_multihost()
        _default_group = _build_world_group()
        _group_map[0] = _default_group
    return _default_group


def is_initialized() -> bool:
    return _default_group is not None


def destroy_process_group(group: Optional[Group] = None) -> None:
    global _default_group
    if group is None:
        _group_map.clear()
        _default_group = None
    else:
        _group_map.pop(group.id, None)
        if _default_group is group:
            _default_group = None


def _get_default_group() -> Group:
    return init_parallel_env()


def get_group(gid: int = 0) -> Optional[Group]:
    return _group_map.get(gid)


def get_rank(group: Optional[Group] = None) -> int:
    """Process index (multi-host controller id). collective.py get_rank."""
    return jax.process_index()


def get_world_size(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.nranks
    return len(jax.devices())


def new_group(ranks: Optional[Sequence[int]] = None, backend=None, timeout=None) -> Group:
    """collective.py:208 parity: a group over a subset of devices.

    The subset becomes its own 1-axis submesh.  Constraint (hardware truth,
    not a software limit): ranks should be contiguous-strided so the submesh
    rides ICI; arbitrary subsets still work but may route over DCN.
    """
    devices = jax.devices()
    if ranks is None:
        ranks = list(range(len(devices)))
    ranks = sorted(int(r) for r in ranks)
    if any(r < 0 or r >= len(devices) for r in ranks):
        raise InvalidArgumentError(
            "new_group ranks %s out of range [0, %d)" % (ranks, len(devices)))
    mesh = Mesh(np.array([devices[r] for r in ranks]), ("sub",))
    g = Group(ranks=ranks, mesh=mesh, axis_name="sub")
    _group_map[g.id] = g
    return g


# -- helpers ----------------------------------------------------------------

def _unwrap(x):
    if isinstance(x, Tensor):
        return x.value
    return jnp.asarray(x)


def _wrap_like(raw, template):
    if isinstance(template, Tensor):
        return Tensor(raw, stop_gradient=True)
    return raw


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _axis_bound(axis_name: str) -> bool:
    """True when ``axis_name`` is a bound shard_map/pmap axis."""
    try:
        axis_size(axis_name)
        return True
    except (NameError, KeyError, ValueError):
        return False


def _eager_collective(group: Group, per_shard_fn, x, out_spec=None, in_spec=None):
    """Run a per-rank collective body over the group's mesh on a stacked input.

    ``x``: global view with rank axis leading (shape ``[nranks, ...]``).
    ``per_shard_fn(local)``: the traced per-rank body (sees ``[...]``).
    """
    ax = group.axis_name
    in_spec = P(ax) if in_spec is None else in_spec
    out_spec = P(ax) if out_spec is None else out_spec
    fn = shard_map(
        per_shard_fn, mesh=group.mesh, in_specs=(in_spec,), out_specs=out_spec)
    return fn(x)


def _check_rank_axis(x, group: Group, api: str):
    if x.ndim == 0 or x.shape[0] != group.nranks:
        raise InvalidArgumentError(
            "%s (eager/global view): leading axis must be the rank axis of "
            "size %d, got shape %s. Inside shard_map/pjit pass the local "
            "shard instead." % (api, group.nranks, tuple(x.shape)))


def _root_index(rank: int, group: Group, api: str) -> int:
    """Map a global root rank to its index along the group axis."""
    idx = group.get_group_rank(rank)
    if idx < 0:
        raise InvalidArgumentError(
            "%s: root rank %d is not a member of %r" % (api, rank, group))
    return idx


def _reduce_body(op, axis_name):
    if op == ReduceOp.SUM:
        return lambda v: lax.psum(v, axis_name)
    if op == ReduceOp.MAX:
        return lambda v: lax.pmax(v, axis_name)
    if op == ReduceOp.MIN:
        return lambda v: lax.pmin(v, axis_name)
    if op == ReduceOp.PROD:
        return lambda v: jnp.prod(lax.all_gather(v, axis_name), axis=0)
    if op == ReduceOp.AVG:
        return lambda v: lax.pmean(v, axis_name)
    raise InvalidArgumentError("unknown ReduceOp %r" % (op,))


# -- collectives ------------------------------------------------------------

def all_reduce(tensor, op=ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True, use_calc_stream: bool = False):
    """collective.py:415 parity.

    Traced: local shard in, reduced value out (``lax.psum`` et al).
    Eager: ``[nranks, ...]`` in, ``[nranks, ...]`` out (every rank's copy of
    the reduction — all slices equal, matching per-rank in-place semantics).
    """
    group = group or _get_default_group()
    raw = _unwrap(tensor)
    body = _reduce_body(op, group.axis_name)
    if _in_trace(raw) and _axis_bound(group.axis_name):
        return _wrap_like(body(raw), tensor)
    _check_rank_axis(raw, group, "all_reduce")

    def per_rank(local):
        # local: [1, ...] slice of the stacked view
        return body(local)

    out = _eager_collective(group, per_rank, raw)
    if isinstance(tensor, Tensor):  # paddle in-place contract
        tensor.set_value(out)
        return tensor
    return out


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM, group: Optional[Group] = None,
           sync_op: bool = True):
    """collective.py:496 parity. Result is defined on ``dst``; other ranks'
    slots hold their input unchanged (matching NCCL reduce leaving non-root
    buffers untouched)."""
    group = group or _get_default_group()
    raw = _unwrap(tensor)
    body = _reduce_body(op, group.axis_name)
    dst_local = _root_index(dst, group, "reduce")
    if _in_trace(raw) and _axis_bound(group.axis_name):
        reduced = body(raw)
        idx = lax.axis_index(group.axis_name)
        return _wrap_like(jnp.where(idx == dst_local, reduced, raw), tensor)
    _check_rank_axis(raw, group, "reduce")

    def per_rank(local):
        reduced = body(local)
        idx = lax.axis_index(group.axis_name)
        return jnp.where(idx == dst_local, reduced, local)

    out = _eager_collective(group, per_rank, raw)
    if isinstance(tensor, Tensor):  # paddle in-place contract
        tensor.set_value(out)
        return tensor
    return out


def all_gather(tensor_list: Optional[List], tensor=None,
               group: Optional[Group] = None, sync_op: bool = True):
    """collective.py:584 parity.

    Traced: local ``[...]`` in → stacked ``[nranks, ...]`` out.
    Eager: stacked ``[nranks, ...]`` in → per-rank slices appended to
    ``tensor_list`` (every rank gathers the same full set).
    Call as ``all_gather(lst, t)`` (paddle style) or ``out = all_gather(t)``.
    """
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    group = group or _get_default_group()
    raw = _unwrap(tensor)
    if _in_trace(raw) and _axis_bound(group.axis_name):
        out = lax.all_gather(raw, group.axis_name)
        if tensor_list is not None:
            tensor_list.extend(_wrap_like(out[i], tensor) for i in range(group.nranks))
        return _wrap_like(out, tensor)
    _check_rank_axis(raw, group, "all_gather")
    if tensor_list is not None:
        tensor_list.extend(_wrap_like(raw[i], tensor) for i in range(group.nranks))
        return tensor_list
    return _wrap_like(raw, tensor)


def reduce_scatter(tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True):
    """``paddle.distributed.reduce_scatter`` parity (communication/reduce_scatter).

    Traced: local ``[n*k, ...]`` in → reduced own chunk ``[k, ...]`` out;
    the list form is this rank's ``n`` chunks (paddle semantics).
    Eager: stacked ``[nranks, n*k, ...]`` in → ``[nranks, k, ...]`` out
    (rank i's slot holds the i-th reduced chunk); the list form is the
    global view — ``nranks`` per-rank tensors.
    Call as ``reduce_scatter(out, in_)`` (paddle style) or ``out = reduce_scatter(in_)``.
    """
    out_slot = None
    src = tensor
    if tensor_or_tensor_list is not None:
        out_slot, src = tensor, tensor_or_tensor_list
    group = group or _get_default_group()
    n = group.nranks
    template = src[0] if isinstance(src, (list, tuple)) else src
    if isinstance(src, (list, tuple)):
        raws = [_unwrap(t) for t in src]
        traced = _in_trace(raws[0]) and _axis_bound(group.axis_name)
        if traced:  # paddle per-rank chunks → concat to [n*k, ...]
            if len(raws) != n:
                raise InvalidArgumentError(
                    "reduce_scatter list form: need %d chunks, got %d"
                    % (n, len(raws)))
            raw = jnp.concatenate(raws, axis=0)
        else:  # global view: one tensor per rank
            if len(raws) != n:
                raise InvalidArgumentError(
                    "reduce_scatter list form: need one tensor per rank "
                    "(%d), got %d" % (n, len(raws)))
            raw = jnp.stack(raws, axis=0)
    else:
        raw = _unwrap(src)
        traced = _in_trace(raw) and _axis_bound(group.axis_name)

    def body(local, scatter_dim):
        if op == ReduceOp.SUM:
            return lax.psum_scatter(
                local, group.axis_name, scatter_dimension=scatter_dim,
                tiled=True)
        if op == ReduceOp.AVG:
            return lax.psum_scatter(
                local, group.axis_name, scatter_dimension=scatter_dim,
                tiled=True) / n
        red = {ReduceOp.MAX: jnp.max, ReduceOp.MIN: jnp.min,
               ReduceOp.PROD: jnp.prod}.get(op)
        if red is None:
            raise InvalidArgumentError("unknown ReduceOp %r" % (op,))
        full = red(lax.all_gather(local, group.axis_name), axis=0)
        k = full.shape[scatter_dim] // n
        idx = lax.axis_index(group.axis_name)
        return lax.dynamic_slice_in_dim(full, idx * k, k, axis=scatter_dim)

    if traced:
        out = body(raw, 0)
    else:
        _check_rank_axis(raw, group, "reduce_scatter")
        out = _eager_collective(group, lambda local: body(local, 1), raw)
    if out_slot is not None and isinstance(out_slot, Tensor):
        out_slot.set_value(out)
        return out_slot
    return _wrap_like(out, template)


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True, use_calc_stream: bool = False):
    """collective.py:332 parity.

    Traced: every rank gets rank-``src``'s value.
    Eager: stacked ``[nranks, ...]`` in → every slot = slice ``src``.
    """
    group = group or _get_default_group()
    raw = _unwrap(tensor)
    src_local = _root_index(src, group, "broadcast")
    if _in_trace(raw) and _axis_bound(group.axis_name):
        out = lax.all_gather(raw, group.axis_name)[src_local]
        return _wrap_like(out, tensor)
    _check_rank_axis(raw, group, "broadcast")

    def per_rank(local):
        full = lax.all_gather(local[0], group.axis_name)
        return full[src_local][None]

    out = _eager_collective(group, per_rank, raw)
    if isinstance(tensor, Tensor):
        tensor.set_value(out)
        return tensor
    return out


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    """collective.py:678 parity.

    Traced: each rank receives its chunk of rank-``src``'s ``[n*k, ...]``.
    Eager: pass ``tensor_list`` of ``nranks`` arrays (the root's chunks) —
    returns the stacked per-rank result ``[nranks, ...]``.
    """
    group = group or _get_default_group()
    n = group.nranks
    src_local = _root_index(src, group, "scatter")
    if tensor_list is not None:
        # eager list form: rank i receives chunk i → stacked global view
        stacked = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
        if isinstance(tensor, Tensor) and tuple(tensor.shape) == tuple(stacked.shape):
            tensor.set_value(stacked)
            return tensor
        return _wrap_like(stacked, tensor)
    raw = _unwrap(tensor)
    if _in_trace(raw) and _axis_bound(group.axis_name):
        full = lax.all_gather(raw, group.axis_name)[src_local]
        k = full.shape[0] // n
        idx = lax.axis_index(group.axis_name)
        return _wrap_like(lax.dynamic_slice_in_dim(full, idx * k, k, axis=0), tensor)
    _check_rank_axis(raw, group, "scatter")

    def per_rank(local):
        full = lax.all_gather(local[0], group.axis_name)[src_local]
        k = full.shape[0] // n
        idx = lax.axis_index(group.axis_name)
        return lax.dynamic_slice_in_dim(full, idx * k, k, axis=0)[None]

    return _wrap_like(_eager_collective(group, per_rank, raw), tensor)


def alltoall(in_tensor_or_list, out_tensor_or_list=None,
             group: Optional[Group] = None, sync_op: bool = True):
    """collective.py:1456 parity (the EP/Ulysses building block).

    Traced: local ``[n*k, ...]`` in → ``[n*k, ...]`` out where chunk j of the
    output is rank j's chunk i (``lax.all_to_all`` over the group axis).
    Eager: stacked ``[nranks, n*k, ...]`` → transposed-chunk stacked result;
    the list form is the same global view as a list of ``nranks`` per-rank
    tensors (each ``[n*k, ...]``), returning the per-rank result list.
    """
    group = group or _get_default_group()
    n = group.nranks
    was_list = isinstance(in_tensor_or_list, (list, tuple))
    if was_list:
        if len(in_tensor_or_list) != n:
            raise InvalidArgumentError(
                "alltoall list form: need %d tensors, got %d"
                % (n, len(in_tensor_or_list)))
        raws = [_unwrap(t) for t in in_tensor_or_list]
        traced = _in_trace(raws[0]) and _axis_bound(group.axis_name)
        # traced: this rank's n outgoing chunks → concat [n*k, ...];
        # eager: global view, one [n*k, ...] tensor per rank → stack
        raw = (jnp.concatenate(raws, axis=0) if traced
               else jnp.stack(raws, axis=0))
    else:
        raw = _unwrap(in_tensor_or_list)
        traced = _in_trace(raw) and _axis_bound(group.axis_name)
    if traced:
        out = lax.all_to_all(
            raw, group.axis_name, split_axis=0, concat_axis=0, tiled=True)
    else:
        _check_rank_axis(raw, group, "alltoall")

        def per_rank(local):
            return lax.all_to_all(
                local, group.axis_name, split_axis=1, concat_axis=1, tiled=True)

        out = _eager_collective(group, per_rank, raw)
    if was_list:
        if traced:  # split received [n*k, ...] back into n chunks
            k = out.shape[0] // n
            outs = [_wrap_like(out[i * k:(i + 1) * k], in_tensor_or_list[i])
                    for i in range(n)]
        else:
            outs = [_wrap_like(out[i], in_tensor_or_list[i]) for i in range(n)]
        if isinstance(out_tensor_or_list, list):
            out_tensor_or_list.extend(outs)
        return outs
    return _wrap_like(out, in_tensor_or_list)


all_to_all = alltoall


def send(tensor, dst: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """collective.py:1515 parity — intentionally unsupported as-is.

    Point-to-point with a per-rank ``dst`` has no single-controller SPMD
    form (there is one program, not per-rank programs); always raises with
    a pointer to ``distributed.p2p.send_next/send_prev`` (static ppermute
    shifts), which is the form pipeline schedules actually need.
    """
    raise InvalidArgumentError(
        "send/recv with a per-rank dst is not expressible as one SPMD "
        "program under a single controller; use distributed.p2p.send_next/"
        "send_prev (static ppermute shift) inside shard_map — the form "
        "pipeline schedules actually need")


def recv(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """See ``send``."""
    return send(tensor, src, group, sync_op)


def isend(tensor, dst: int = 0, group: Optional[Group] = None):
    return send(tensor, dst, group)


def irecv(tensor, src: int = 0, group: Optional[Group] = None):
    return recv(tensor, src, group)


class _P2P:
    """Static-shift point-to-point (pipeline p2p_communication.py:21 analog).

    ``send_next``/``send_prev`` rotate values along the group axis by ±1 with
    ``lax.ppermute`` — the SPMD-expressible form of the reference's
    send/recv pairs between adjacent pipeline stages.
    """

    @staticmethod
    def send_next(x, group: Optional[Group] = None):
        group = group or _get_default_group()
        n = group.nranks
        raw = _unwrap(x)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return _wrap_like(lax.ppermute(raw, group.axis_name, perm), x)

    @staticmethod
    def send_prev(x, group: Optional[Group] = None):
        group = group or _get_default_group()
        n = group.nranks
        raw = _unwrap(x)
        perm = [(i, (i - 1) % n) for i in range(n)]
        return _wrap_like(lax.ppermute(raw, group.axis_name, perm), x)


p2p = _P2P()


def barrier(group: Optional[Group] = None) -> None:
    """collective.py:275 parity: fence host against all enqueued device work.

    XLA orders device-side work itself; the host-visible meaning of barrier
    is "everything dispatched has completed" — block_until_ready on a token
    reduction across the group's devices.
    """
    group = group or _get_default_group()
    tok = jnp.zeros((group.nranks,), jnp.int32)
    tok = jax.device_put(tok, NamedSharding(group.mesh, P(group.axis_name)))
    jax.block_until_ready(tok.sum())


def wait(tensor, group: Optional[Group] = None, use_calc_stream: bool = True) -> None:
    """collective.py wait parity: block host until ``tensor`` is computed."""
    jax.block_until_ready(_unwrap(tensor))


class stream:
    """``paddle.distributed.stream`` namespace parity: on TPU the compiler
    schedules communication; the stream-controlled variants are the plain
    collectives."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce = staticmethod(reduce)
    broadcast = staticmethod(broadcast)
    scatter = staticmethod(scatter)
    alltoall = staticmethod(alltoall)
    reduce_scatter = staticmethod(reduce_scatter)


_split_layers: dict = {}


def get_split_layer(name: str):
    """The parallel layer a named :func:`split` call site created (its
    parameters feed an optimizer's parameter list)."""
    if name not in _split_layers:
        raise InvalidArgumentError("no split layer named %r" % name)
    return _split_layers[name]


def split(x, size, operation: str, axis: int = 0, num_partitions: int = 1,
          gather_out: bool = True, weight_attr=None, bias_attr=None,
          name=None):
    """collective.py:1283 parity: model-parallel linear/embedding in one
    call.  Builds the corresponding parallel layer over the active fleet
    mp group and applies it — the reference's program-rewriting becomes
    GSPMD placement inside the layer.

    With ``name=`` the layer (and its weights) is created once and reused
    on every later call with that name (:func:`get_split_layer` exposes it
    for the optimizer).  Unnamed calls create fresh, uncached weights each
    time — the reference's build-once semantics — and warn.
    """
    from .meta_parallel.mp_layers import (ColumnParallelLinear,
                                          RowParallelLinear,
                                          VocabParallelEmbedding, _mp_group)

    group = _mp_group(None)
    mp_deg = int(group.mesh.shape[group.axis_name])
    if num_partitions != 1 and num_partitions != mp_deg:
        raise InvalidArgumentError(
            "num_partitions %d does not match the mp degree %d"
            % (num_partitions, mp_deg))
    if name is None:
        # unnamed call: fresh weights every call (reference build-time
        # semantics — split is called once while constructing the model);
        # name= opts into call-site reuse for eager loops
        import warnings

        warnings.warn(
            "distributed.split without name= creates new weights on every "
            "call; pass name='...' to reuse one layer across steps",
            stacklevel=2)
        key = None
    else:
        key = name
    layer = _split_layers.get(key) if key is not None else None
    if layer is None:
        if operation == "embedding":
            layer = VocabParallelEmbedding(int(size[0]), int(size[1]),
                                           weight_attr=weight_attr,
                                           mp_group=group)
        elif operation != "linear":
            raise InvalidArgumentError(
                "split supports operation='linear' or 'embedding', got %r"
                % operation)
        elif axis == 1:
            layer = ColumnParallelLinear(int(size[0]), int(size[1]),
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out,
                                         mp_group=group)
        elif axis == 0:
            layer = RowParallelLinear(int(size[0]), int(size[1]),
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False,
                                      input_is_parallel=False,
                                      mp_group=group)
        else:
            raise InvalidArgumentError("split axis must be 0 or 1")
        if key is not None:
            _split_layers[key] = layer
    return layer(x)
