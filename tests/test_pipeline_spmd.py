"""SPMD pipeline-parallel tests (VERDICT r2 item #1).

Mirrors the reference's pipeline semantics tests: micro-batch loss-mean
parity with plain training (``section_worker.cc:167-175`` 1F1B math,
``fleet/meta_parallel/pipeline_parallel.py``), plus the TPU-native placement
guarantee — stage parameters live on disjoint device sets of the ``pp``
mesh axis.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
import paddle_tpu.tensor as T
from paddle_tpu.distributed.meta_parallel.pp_layers import (
    LayerDesc, PipelineLayer)
from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
    PipelineParallel)
from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
    partition_pipeline)
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.nn.layer.norm import LayerNorm
from paddle_tpu.nn.layer.transformer import TransformerEncoderLayer

D, V, S, HEADS, FF = 16, 32, 8, 2, 32


class Block(pt.nn.Layer):
    def __init__(self, dropout=0.0):
        super().__init__()
        self.l = TransformerEncoderLayer(D, HEADS, FF, dropout=dropout)

    def forward(self, x):
        return self.l(x)


class Embed(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.emb = Embedding(V, D)

    def forward(self, ids):
        return self.emb(ids)


class Head(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.norm = LayerNorm(D)
        self.proj = Linear(D, V)

    def forward(self, h):
        return self.proj(self.norm(h))


def loss_fn(logits, labels):
    v = logits.shape[-1]
    return F.cross_entropy(
        T.reshape(logits, [-1, v]), T.reshape(labels, [-1]),
        reduction="mean")


class Seq(pt.nn.Layer):
    def __init__(self, layers):
        super().__init__()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)
        self._ls = layers

    def forward(self, x):
        for l in self._ls:
            x = l(x)
        return x


def _build_layers(n_blocks):
    pt.seed(0)
    return [Embed()] + [Block() for _ in range(n_blocks)] + [Head()]


def _copy_weights(src_layers, dst_layers):
    for a, b in zip(src_layers, dst_layers):
        b.set_state_dict(a.state_dict())


def _train_ref(layers, xs, ys, M, steps, lr=1e-3, grad_clip=None):
    """Plain microbatch grad accumulation on one device — the math PP must
    reproduce (test_dist_base.check_with_place parity pattern)."""
    seq = Seq(layers)
    opt = pt.optimizer.AdamW(lr, parameters=seq.parameters(),
                             grad_clip=grad_clip)
    losses = []
    for step in range(steps):
        x, y = pt.to_tensor(xs[step]), pt.to_tensor(ys[step])
        B = xs[step].shape[0]
        mb = B // M
        tot = 0.0
        for i in range(M):
            out = seq(x[i * mb:(i + 1) * mb])
            l = loss_fn(out, y[i * mb:(i + 1) * mb])
            (l * (1.0 / M)).backward()
            tot += float(l.value)
        opt.step()
        opt.clear_grad()
        losses.append(tot / M)
    return losses


def _make_data(steps, B):
    rng = np.random.RandomState(0)
    xs = rng.randint(0, V, (steps, B, S)).astype("int32")
    ys = rng.randint(0, V, (steps, B, S)).astype("int64")
    return xs, ys


class Strat:
    def __init__(self, k):
        self.pipeline_configs = {"accumulate_steps": k}


@pytest.mark.parametrize("pp_degree,n_blocks,B,M", [
    (4, 4, 8, 4), (2, 4, 16, 4),
    (2, 4, 12, 3),  # M % pp != 0: replicated-suffix fallback path
])
def test_pipeline_spmd_loss_parity(pp_degree, n_blocks, B, M):
    steps = 3
    xs, ys = _make_data(steps, B)

    ref_layers = _build_layers(n_blocks)
    pipe_layers = _build_layers(n_blocks)
    _copy_weights(ref_layers, pipe_layers)

    ref_losses = _train_ref(ref_layers, xs, ys, M, steps)

    pl = PipelineLayer(pipe_layers, num_stages=pp_degree, loss_fn=loss_fn)
    engine = PipelineParallel(pl, strategy=Strat(M))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    pp_losses = [
        float(engine.train_batch(
            (pt.to_tensor(xs[i]), pt.to_tensor(ys[i])), opt).value)
        for i in range(steps)
    ]
    assert engine._spmd_step is not None, "SPMD engine must be active"
    np.testing.assert_allclose(ref_losses, pp_losses, rtol=1e-4, atol=1e-5)


def test_pipeline_stage_placement_disjoint():
    """Stage parameters must live on disjoint device sets (the NamedSharding
    placement pp_layers.py promises)."""
    pp_degree, M, B = 4, 4, 8
    xs, ys = _make_data(1, B)
    pl = PipelineLayer(_build_layers(4), num_stages=pp_degree,
                       loss_fn=loss_fn)
    engine = PipelineParallel(pl, strategy=Strat(M))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    engine.train_batch((pt.to_tensor(xs[0]), pt.to_tensor(ys[0])), opt)
    devsets = [engine.stage_devices(s) for s in range(pp_degree)]
    for s, ds in enumerate(devsets):
        assert ds, "stage %d has no devices" % s
    for i in range(pp_degree):
        for j in range(i + 1, pp_degree):
            assert not (devsets[i] & devsets[j]), \
                "stages %d and %d share devices" % (i, j)
    # together the stages cover the whole mesh
    assert set().union(*devsets) == set(jax.devices())


def test_pipeline_partition_prefix_suffix():
    pl = PipelineLayer(_build_layers(4), num_stages=4, loss_fn=loss_fn)
    parts = partition_pipeline(pl)
    assert parts is not None
    prefix, core, suffix = parts
    assert len(prefix) == 1 and isinstance(prefix[0][0], Embed)
    assert len(core) == 4 and all(len(c) == 1 for c in core)
    assert len(suffix) == 1 and isinstance(suffix[0][0], Head)


def test_pipeline_partition_remainder_joins_prefix():
    # 5 blocks over pp=2 -> 2x2 core, 1 block replicated with the prefix
    pl = PipelineLayer(_build_layers(5), num_stages=2, loss_fn=loss_fn)
    prefix, core, suffix = partition_pipeline(pl)
    assert len(prefix) == 2  # Embed + leftover Block
    assert [len(c) for c in core] == [2, 2]


def test_pipeline_hetero_falls_back():
    """No homogeneous run long enough -> engine falls back to grad accum."""
    pt.seed(0)
    layers = [Embed(), Block(), Head()]
    pl = PipelineLayer(layers, num_stages=2, loss_fn=loss_fn)
    assert partition_pipeline(pl) is None
    engine = PipelineParallel(pl, strategy=Strat(2))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    xs, ys = _make_data(1, 4)
    loss = engine.train_batch((pt.to_tensor(xs[0]), pt.to_tensor(ys[0])), opt)
    assert np.isfinite(float(loss.value))
    assert engine._spmd_step is None


def test_pipeline_state_dict_syncs_stacked_weights():
    pp_degree, M, B = 4, 4, 8
    xs, ys = _make_data(2, B)
    layers = _build_layers(4)
    pl = PipelineLayer(layers, num_stages=pp_degree, loss_fn=loss_fn)
    engine = PipelineParallel(pl, strategy=Strat(M))
    opt = pt.optimizer.AdamW(1e-2, parameters=pl.parameters())
    before = {k: np.asarray(v.value).copy()
              for k, v in pl.state_dict().items()}
    for i in range(2):
        engine.train_batch((pt.to_tensor(xs[i]), pt.to_tensor(ys[i])), opt)
    engine.state_dict()  # triggers the stacked->Parameter sync
    after = pl.state_dict()
    changed = [k for k in before
               if not np.allclose(before[k], np.asarray(after[k].value))]
    assert changed, "state_dict must reflect trained stacked weights"
    # stacked slices and layer Parameters agree after sync
    for j, p in enumerate(engine._spmd_step._template):
        s0 = np.asarray(engine._spmd_step._stacked[j][0])
        np.testing.assert_allclose(np.asarray(p.value), s0, rtol=1e-6)


def test_pipeline_with_global_norm_clip_parity():
    steps, B, M, ppd = 2, 8, 4, 4
    xs, ys = _make_data(steps, B)
    clip = pt.nn.ClipGradByGlobalNorm(0.05)
    ref_layers = _build_layers(4)
    pipe_layers = _build_layers(4)
    _copy_weights(ref_layers, pipe_layers)
    ref_losses = _train_ref(ref_layers, xs, ys, M, steps,
                            grad_clip=pt.nn.ClipGradByGlobalNorm(0.05))
    pl = PipelineLayer(pipe_layers, num_stages=ppd, loss_fn=loss_fn)
    engine = PipelineParallel(pl, strategy=Strat(M))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters(),
                             grad_clip=clip)
    pp_losses = [
        float(engine.train_batch(
            (pt.to_tensor(xs[i]), pt.to_tensor(ys[i])), opt).value)
        for i in range(steps)
    ]
    np.testing.assert_allclose(ref_losses, pp_losses, rtol=1e-4, atol=1e-5)


def test_pipeline_optimizer_state_checkpoint_complete():
    """Outer (embedding/head) optimizer states must sync back too, and a
    rebuilt engine must warm-start from existing optimizer states."""
    ppd, M, B = 4, 4, 8
    xs, ys = _make_data(3, B)
    pl = PipelineLayer(_build_layers(4), num_stages=ppd, loss_fn=loss_fn)
    engine = PipelineParallel(pl, strategy=Strat(M))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    for i in range(2):
        engine.train_batch((pt.to_tensor(xs[i]), pt.to_tensor(ys[i])), opt)
    engine._sync_if_needed()
    sd = opt.state_dict()
    # every trainable parameter has moments, and none are all-zero
    pnames = [p.name for p in pl.parameters() if not p.stop_gradient]
    for n in pnames:
        key = "%s__moment1" % n
        assert key in sd, "missing optimizer state for %r" % n
        assert float(abs(sd[key].value).sum()) > 0, \
            "optimizer state for %r was never updated (stale step-0)" % n
    # warm rebuild: a new engine stacks the existing states, not zeros
    engine2 = PipelineParallel(pl, strategy=Strat(M))
    loss = engine2.train_batch(
        (pt.to_tensor(xs[2]), pt.to_tensor(ys[2])), opt)
    assert np.isfinite(float(loss.value))
    st0 = engine2._spmd_step._stacked_states[0]
    assert float(np.asarray(st0["beta1_pow"]).max()) < 1.0, \
        "warm rebuild must inherit beta_pow from prior steps"


def test_pipeline_homogeneous_no_prefix():
    """Embed-free homogeneous pipeline (rank-preserving, float inputs)."""
    pt.seed(0)
    blocks = [Block() for _ in range(4)]
    pl = PipelineLayer(
        blocks, num_stages=2,
        loss_fn=lambda out, tgt: F.mse_loss(out, tgt))
    engine = PipelineParallel(pl, strategy=Strat(2))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    rng = np.random.RandomState(0)
    x = rng.randn(8, S, D).astype("float32")
    t = rng.randn(8, S, D).astype("float32")
    l0 = float(engine.train_batch((pt.to_tensor(x), pt.to_tensor(t)), opt).value)
    before = engine._spmd_step._stacked[0]
    l1 = float(engine.train_batch((pt.to_tensor(x), pt.to_tensor(t)), opt).value)
    assert engine._spmd_step is not None
    assert np.isfinite(l0) and l1 < l0
    # the CPU step donates nothing: loaded from the persistent compile
    # cache with donated arguments, XLA:CPU aborted on this second call
    assert not before.is_deleted()


def test_pipeline_rank_preserving_prefix_remainder():
    """5 blocks over pp=2: the remainder block joins the prefix, which
    preserves input rank — the h0 spec must be derived, not assumed."""
    pt.seed(0)
    blocks = [Block() for _ in range(5)]
    pl = PipelineLayer(
        blocks, num_stages=2,
        loss_fn=lambda out, tgt: F.mse_loss(out, tgt))
    engine = PipelineParallel(pl, strategy=Strat(2))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    rng = np.random.RandomState(0)
    x = rng.randn(8, S, D).astype("float32")
    t = rng.randn(8, S, D).astype("float32")
    loss = engine.train_batch((pt.to_tensor(x), pt.to_tensor(t)), opt)
    assert engine._spmd_step is not None
    assert np.isfinite(float(loss.value))


@pytest.mark.skip(reason="pre-existing seed failure: partial-manual shard_map lowers a PartitionId op this jax build's SPMD partitioner rejects (UNIMPLEMENTED); pp-with-mp needs a newer jax")
def test_pipeline_with_tensor_parallel_stages():
    """BASELINE config #5 shape: pp x mp (x dp) in ONE compiled step —
    stage rotation manual (ppermute), tensor parallelism inside stages
    GSPMD-managed via partial-manual shard_map.  Loss parity with plain
    single-device microbatch training proves the composition is placement,
    not math."""
    from jax.sharding import Mesh

    from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
        PipelineTrainStep)

    steps, M, B = 3, 2, 8
    xs, ys = _make_data(steps, B)
    ref_layers = _build_layers(4)
    pipe_layers = _build_layers(4)
    _copy_weights(ref_layers, pipe_layers)
    ref_losses = _train_ref(ref_layers, xs, ys, M, steps)

    pl = PipelineLayer(pipe_layers, num_stages=2, loss_fn=loss_fn)
    parts = partition_pipeline(pl)
    assert parts is not None
    _, core, _ = parts

    # Megatron placement for the stage template (shared library helper)
    from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
        megatron_param_spec)

    mp_spec = megatron_param_spec(core[0])
    assert mp_spec is not None

    devices = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devices, ("dp", "pp", "mp"))
    opt = pt.optimizer.AdamW(1e-3, parameters=pl.parameters())
    engine = PipelineTrainStep(pl, opt, mesh, microbatches=M,
                               recompute=False, mp_param_spec=mp_spec)

    # placement check: a column-parallel stacked weight is sharded pp x mp
    from jax.sharding import PartitionSpec as P

    col = next((sh for sh in engine._core_shardings
                if sh.spec == P("pp", None, "mp")), None)
    assert col is not None, [sh.spec for sh in engine._core_shardings]
    # param-shaped optimizer slots follow the mp placement (memory claim)
    mstate = next(
        (st for st in engine._stacked_states
         if any(getattr(l.sharding, "spec", None) == P("pp", None, "mp")
                for l in jax.tree_util.tree_leaves(st))), None)
    assert mstate is not None

    pp_losses = [float(engine(pt.to_tensor(xs[i]),
                              pt.to_tensor(ys[i])).value)
                 for i in range(steps)]
    np.testing.assert_allclose(ref_losses, pp_losses, rtol=1e-4, atol=1e-5)
