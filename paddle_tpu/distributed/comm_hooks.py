"""Compressed gradient communication inside the compiled DP step.

Reference parity: DGC's sparse allreduce
(``paddle/fluid/framework/details/sparse_all_reduce_op_handle.cc:1`` —
each rank encodes its top-k (index, value) pairs, allgathers the encodings,
and densifies locally) and the fp16 allreduce rewrite
(``fleet/meta_optimizers/fp16_allreduce_optimizer.py:20`` — gradients cross
the wire as fp16 and are cast back after the reduce).

TPU-native design: the plain DP path lets GSPMD insert a dense fp32
all-reduce.  To actually change what crosses the wire, this module builds
the train step as an explicit ``shard_map`` over the data-parallel axis —
forward/backward run per-device on the local batch shard, and the gradient
synchronization is hand-written:

- ``fp16``: ``lax.psum`` of the fp16-cast gradient (the reduce operand is
  half-width on ICI), cast back to fp32 for the update.
- ``dgc``: per-device momentum-corrected error feedback (DGC paper §3),
  local top-k selection, ``lax.all_gather`` of k (index, value) pairs —
  2k words per device instead of n — then a local dense scatter-add.
  Residuals stay per-device (sharded [dp, ...] state), exactly like the
  reference's per-rank ``DGCMomentumOp`` buffers.

The eager wrappers in ``fleet.meta_optimizers`` (DGCOptimizer /
FP16AllreduceOptimizer) reproduce the update *math* for eager loops; this
step is the compiled path where the communication itself is compressed.
``tests/test_comm_hooks.py`` asserts via jaxpr inspection that no
param-sized fp32 tensor is ever reduced.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.errors import InvalidArgumentError
from ..core.random import next_key, rng_guard
from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer

__all__ = ["CompressedAllReduceStep"]


def _unwrap(v):
    return v.value if isinstance(v, Tensor) else v


class CompressedAllReduceStep:
    """One-compile DP training step with compressed gradient communication.

    ``compression``: ``'fp16'`` (half-precision reduce) or ``'dgc'``
    (top-k sparse allgather with per-device error feedback).
    ``sparsity``: DGC fraction of entries NOT communicated (0.999 -> top
    0.1%).  ``momentum``: DGC momentum-correction factor.

    Same calling convention as ``paddle_tpu.jit.TrainStep``:
    ``step(*batch) -> loss`` with ``loss_fn(model, *batch) -> scalar``.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 group=None, compression: str = "fp16",
                 sparsity: float = 0.999, momentum: float = 0.9,
                 rampup_begin_step: int = 0):
        if compression not in ("fp16", "dgc"):
            raise InvalidArgumentError(
                "compression must be 'fp16' or 'dgc', got %r" % compression)
        from ..jit import _StateBinding
        from .collective import init_parallel_env

        self._model = model
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self.group = group or init_parallel_env()
        self.mesh = self.group.mesh
        self.axis = self.group.axis_name
        self.dp = self.group.nranks
        self.compression = compression
        self.sparsity = float(sparsity)
        self.momentum = float(momentum)
        self.rampup_begin_step = int(rampup_begin_step)
        self._step_count = 0

        self._binding = _StateBinding(model)
        params = self._binding.params
        if optimizer._parameter_list is None:
            optimizer._parameter_list = params
        opt_ids = {id(p) for p in optimizer._parameter_list
                   if not p.stop_gradient}
        self._opt_params = [p for p in params if id(p) in opt_ids]
        for p in self._opt_params:
            optimizer._state_for(p)
        # replicate params/buffers over the dp mesh
        repl = NamedSharding(self.mesh, P())
        for p in params:
            p._replace_value(jax.device_put(p._value, repl))
        for b in self._binding.buffers:
            b._replace_value(jax.device_put(b._value, repl))

        if compression == "dgc":
            # per-device residual state: [dp, *param.shape], sharded on dp
            self._uv = []
            for p in self._opt_params:
                shape = (self.dp,) + tuple(p._value.shape)
                sh = NamedSharding(self.mesh,
                                   P(self.axis, *((None,) * p._value.ndim)))
                # two distinct buffers: donation forbids aliased inputs
                self._uv.append(
                    (jax.device_put(jnp.zeros(shape, jnp.float32), sh),
                     jax.device_put(jnp.zeros(shape, jnp.float32), sh)))
        else:
            self._uv = []
        self._jitted = None

    # -- gradient communication hooks (per-device, inside shard_map) ------
    def _sync_fp16(self, g):
        return lax.psum(g.astype(jnp.float16), self.axis) \
            .astype(jnp.float32) / self.dp

    def _sync_dgc(self, g, u, v):
        """DGC §3: momentum correction + error feedback + top-k exchange.
        Returns (mean synced grad, new_u, new_v); u/v are this device's
        residuals."""
        u = self.momentum * u + g
        v = v + u
        flat = v.reshape(-1)
        n = flat.size
        k = max(1, int(round(n * (1.0 - self.sparsity))))
        _, idx = lax.top_k(jnp.abs(flat), k)
        vals = flat[idx]
        # the wire format: k int32 indices + k fp32 values per device
        g_idx = lax.all_gather(idx.astype(jnp.int32), self.axis)   # [dp, k]
        g_val = lax.all_gather(vals, self.axis)                    # [dp, k]
        dense = jnp.zeros((n,), v.dtype).at[g_idx.reshape(-1)].add(
            g_val.reshape(-1), mode="drop") / self.dp
        mask = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
        keep = (~mask).reshape(v.shape)
        return dense.reshape(v.shape), jnp.where(keep, u, 0.0), \
            jnp.where(keep, v, 0.0)

    # -- compiled step ----------------------------------------------------
    def _build(self):
        binding = self._binding
        opt = self._optimizer
        params = binding.params
        opt_ids = {id(p) for p in self._opt_params}
        diff_idx = [i for i, p in enumerate(params) if id(p) in opt_ids]
        diff_params = [params[i] for i in diff_idx]
        axis, dp = self.axis, self.dp
        compression = self.compression

        def per_device(param_vals, opt_states, buf_vals, uv, batch_leaves,
                       key, lr, compress_now):
            # manual region over the dp axis: batch_leaves are local shards,
            # uv leaves are [1, ...] (this device's residuals)
            key = jax.random.fold_in(key, lax.axis_index(axis))

            def forward(dv):
                pv = list(param_vals)
                for i, v in zip(diff_idx, dv):
                    pv[i] = v
                saved = binding.swap_in(pv, buf_vals)
                try:
                    batch = [Tensor(l, stop_gradient=True)
                             if isinstance(l, jax.Array) else l
                             for l in batch_leaves]
                    with rng_guard(key):
                        loss = self._loss_fn(self._model, *batch)
                    loss_raw = _unwrap(loss)
                finally:
                    new_bufs = binding.swap_out(saved)
                return loss_raw, new_bufs

            diff_vals = [param_vals[i] for i in diff_idx]
            (loss, new_bufs), grads = jax.value_and_grad(
                forward, has_aux=True)(diff_vals)

            synced, new_uv = [], []
            for j, g in enumerate(grads):
                gf = g.astype(jnp.float32)
                if compression == "fp16":
                    synced.append(self._sync_fp16(gf).astype(g.dtype))
                else:
                    u, v = uv[j][0][0], uv[j][1][0]
                    sg, nu, nv = self._sync_dgc(gf, u, v)
                    # before rampup: plain (but still fp32-dense) mean sync
                    dense = lax.psum(gf, axis) / dp
                    sg = jnp.where(compress_now, sg, dense)
                    nu = jnp.where(compress_now, nu, u)
                    nv = jnp.where(compress_now, nv, v)
                    synced.append(sg.astype(g.dtype))
                    new_uv.append((nu[None], nv[None]))

            new_diff_vals, new_states = opt._functional_step(
                diff_params, diff_vals, synced, opt_states, lr)
            new_param_vals = list(param_vals)
            for i, v in zip(diff_idx, new_diff_vals):
                new_param_vals[i] = v
            # non-grad buffers (BatchNorm running stats) were updated from
            # each device's local shard; average them so the P() out_spec's
            # replication claim holds and eval sees global-batch statistics
            new_bufs = [lax.pmean(b, axis) if jnp.issubdtype(
                b.dtype, jnp.floating) else b for b in new_bufs]
            loss = lax.pmean(loss, axis)
            return loss, new_param_vals, new_states, new_bufs, \
                (new_uv if compression == "dgc" else uv)

        def _rep(tree):
            return jax.tree.map(lambda l: P(*((None,) * jnp.ndim(l))), tree,
                                is_leaf=lambda x: isinstance(x, jax.Array))

        def step(param_vals, opt_states, buf_vals, uv, batch_leaves, key,
                 lr, compress_now):
            in_specs = (
                _rep(param_vals), _rep(opt_states), _rep(buf_vals),
                jax.tree.map(lambda l: P(axis, *((None,) * (l.ndim - 1))),
                             uv, is_leaf=lambda x: isinstance(x, jax.Array)),
                jax.tree.map(lambda l: P(axis, *((None,) * (l.ndim - 1))),
                             batch_leaves,
                             is_leaf=lambda x: isinstance(x, jax.Array)),
                P(), P(), P(),
            )
            out_specs = (
                P(), _rep(param_vals), _rep(opt_states), _rep(buf_vals),
                jax.tree.map(lambda l: P(axis, *((None,) * (l.ndim - 1))),
                             uv, is_leaf=lambda x: isinstance(x, jax.Array)),
            )
            # replication checking off — same helper the collectives use
            from .collective import shard_map

            fn = shard_map(per_device, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs)
            return fn(param_vals, opt_states, buf_vals, uv, batch_leaves,
                      key, lr, compress_now)

        self._step_fn = step
        self._jitted = jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def __call__(self, *batch):
        binding = self._binding
        opt = self._optimizer
        self._step_count += 1
        param_vals = [p._value for p in binding.params]
        buf_vals = [b._value for b in binding.buffers]
        opt_states = [opt._states[p.name] for p in self._opt_params]
        batch_leaves = []
        for b in batch:
            l = _unwrap(b)
            l = jnp.asarray(l)
            if l.ndim == 0 or l.shape[0] % self.dp != 0:
                raise InvalidArgumentError(
                    "CompressedAllReduceStep: batch dim must be divisible "
                    "by dp=%d" % self.dp)
            batch_leaves.append(l)
        if self._jitted is None:
            self._build()
        key = next_key()
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        compress_now = jnp.asarray(
            self._step_count > self.rampup_begin_step)
        loss, new_param_vals, new_states, new_bufs, self._uv = self._jitted(
            param_vals, opt_states, buf_vals, self._uv, batch_leaves, key,
            lr, compress_now)
        for p, v in zip(binding.params, new_param_vals):
            p._replace_value(v)
        for p, s in zip(self._opt_params, new_states):
            opt._states[p.name] = s
        for b, v in zip(binding.buffers, new_bufs):
            b._replace_value(v)
        return Tensor(loss, stop_gradient=True)
