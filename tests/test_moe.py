"""Expert parallelism (MoELayer): gating math, dense-path parity with a
per-token reference loop, grads, ep-axis placement on the CPU mesh, and a
training step through TrainStep."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as pt
from paddle_tpu.distributed.collective import Group
from paddle_tpu.distributed.meta_parallel import MoELayer, top2_gating


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def test_top2_gating_properties(rng):
    B, S, E, C = 2, 16, 4, 8
    logits = jnp.asarray(rng.randn(B, S, E).astype(np.float32))
    dispatch, combine, aux = top2_gating(logits, capacity=C, top_k=2)
    assert dispatch.shape == (B, S, E, C)
    d = np.asarray(dispatch)
    # each token occupies at most top_k slots, each slot at most one token
    assert d.sum(axis=(2, 3)).max() <= 2.0 + 1e-6
    assert d.sum(axis=(1,)).max() <= 1.0 + 1e-6
    # combine weights are gate probs on dispatched slots only
    c = np.asarray(combine)
    assert ((c > 0) <= (d > 0)).all()
    assert float(aux) > 0.0
    # balanced logits → aux loss near 1 (its minimum for uniform routing)
    uni = top2_gating(jnp.zeros((1, 64, E)), capacity=64, top_k=2)[2]
    assert abs(float(uni) - 1.0) < 0.3


def test_moe_matches_per_token_loop(rng):
    """Dense einsum dispatch == explicit per-token routing (oracle)."""
    B, S, M, H, E = 2, 8, 6, 12, 4
    x = rng.randn(B, S, M).astype(np.float32)
    # capacity_factor large enough that nothing is dropped
    moe = MoELayer(M, H, E, top_k=2, capacity_factor=float(E),
                   activation="relu", renormalize=False)
    out = moe(pt.to_tensor(x))
    wg = np.asarray(moe.gate_weight.value)
    w1, b1 = np.asarray(moe.w1.value), np.asarray(moe.b1.value)
    w2, b2 = np.asarray(moe.w2.value), np.asarray(moe.b2.value)

    def expert(e, v):
        h = np.maximum(v @ w1[e] + b1[e], 0.0)
        return h @ w2[e] + b2[e]

    want = np.zeros_like(x)
    for b in range(B):
        for s in range(S):
            logit = x[b, s] @ wg
            p = np.exp(logit - logit.max())
            p /= p.sum()
            top = np.argsort(-p)[:2]
            for e in top:
                want[b, s] += p[e] * expert(e, x[b, s])
    np.testing.assert_allclose(np.asarray(out.value), want,
                               rtol=2e-4, atol=2e-5)


def test_moe_grads_flow(rng):
    B, S, M, H, E = 2, 8, 4, 8, 4
    x = rng.randn(B, S, M).astype(np.float32)
    moe = MoELayer(M, H, E)
    out = moe(pt.to_tensor(x))
    loss = (out * out).mean() + moe.aux_loss * 0.01
    loss.backward()
    for p in (moe.gate_weight, moe.w1, moe.w2):
        g = np.asarray(p.grad.value)
        assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_moe_ep_placement_parity(rng):
    """Experts sharded over an 8-way ep axis == dense single-device MoE."""
    devs = jax.devices()
    assert len(devs) >= 8
    mesh = Mesh(np.array(devs[:8]), ("ep",))
    group = Group(ranks=list(range(8)), mesh=mesh, axis_name="ep")
    B, S, M, H, E = 2, 16, 6, 12, 8
    x = rng.randn(B, S, M).astype(np.float32)
    pt.seed(3)
    dense = MoELayer(M, H, E)
    pt.seed(3)
    sharded = MoELayer(M, H, E, ep_group=group)
    for pd, ps in zip(dense.parameters(), sharded.parameters()):
        np.testing.assert_array_equal(np.asarray(pd.value),
                                      np.asarray(ps.value))
    # expert weights actually live sharded over the ep axis
    spec = sharded.w1.value.sharding.spec
    assert spec[0] == "ep"
    o_d = dense(pt.to_tensor(x))
    o_s = sharded(pt.to_tensor(x))
    np.testing.assert_allclose(np.asarray(o_d.value), np.asarray(o_s.value),
                               rtol=1e-5, atol=1e-6)


def test_moe_fleet_ep_axis(rng):
    """fleet.init with ep_degree wires the expert group automatically."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import fleet as fleet_singleton

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 1, "ep_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_expert_parallel_world_size() == 8
        moe = MoELayer(4, 8, 8)
        assert moe.ep_group is not None and moe.ep_group.nranks == 8
        assert moe.w1.value.sharding.spec[0] == "ep"
    finally:
        fleet_singleton._initialized = False
        fleet_singleton._hcg = None


def test_moe_trains_under_jit(rng):
    from paddle_tpu.jit import TrainStep

    B, S, M, H, E, V = 4, 8, 16, 32, 4, 50
    xs = rng.randn(B, S, M).astype(np.float32)
    ys = rng.randint(0, V, (B, S)).astype(np.int32)

    class MoEBlock(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.moe = MoELayer(M, H, E)
            self.norm = pt.nn.LayerNorm(M)
            self.head = pt.nn.Linear(M, V)

        def forward(self, x):
            x = x + self.moe(x)  # residual carries dropped tokens
            return self.head(self.norm(x))

    pt.seed(0)
    model = MoEBlock()
    opt = pt.optimizer.Adam(0.01, parameters=model.parameters())

    def loss_fn(m, x, y):
        logits = m(x)
        ce = pt.nn.functional.cross_entropy(
            logits.reshape([-1, V]), y.reshape([-1]))
        return ce + 0.01 * m.moe.aux_loss

    step = TrainStep(model, loss_fn, opt)
    losses = [float(step(xs, ys)) for _ in range(6)]
    assert losses[-1] < losses[0]
    # monitoring after a compiled step must see a concrete value, not a
    # leaked tracer (the buffer write-back path)
    aux = float(model.moe.aux_loss)
    assert np.isfinite(aux) and aux > 0.0


def test_moe_ep_sharding_survives_training(rng):
    """Expert weights must STAY ep-sharded after donated TrainStep updates
    (placement must round-trip through the optimizer)."""
    from paddle_tpu.jit import TrainStep

    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("dp", "ep"))
    group = Group(ranks=list(range(8)), mesh=mesh, axis_name="ep")
    pt.seed(0)
    moe = MoELayer(8, 16, num_experts=4, ep_group=group)
    head = pt.nn.Linear(8, 4)

    class Net(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.moe = moe
            self.head = head

        def forward(self, x):
            return self.head(x + self.moe(x))

    model = Net()
    opt = pt.optimizer.Adam(1e-2, parameters=model.parameters())
    xs = rng.randn(4, 8, 8).astype(np.float32)
    ys = rng.randint(0, 4, (4, 8)).astype(np.int32)

    def loss_fn(m, x, y):
        logits = m(x)
        return pt.nn.functional.cross_entropy(
            pt.reshape(logits, [-1, 4]), pt.reshape(y, [-1]))

    step = TrainStep(model, loss_fn, opt)
    with mesh:
        for _ in range(3):
            step(xs, ys)
    spec = moe.w1.value.sharding.spec
    assert spec[0] == "ep", spec


# -- the serving expert layer's router as data, and a share of the experts -----
# (``F.route_top_k``, ``F.sparse_experts``, ``nn.SparseExperts``: the softmax
# rule's own tests are tests/test_block_diffusion.py's)

def _plain_router(scores, top_k, n_group, topk_group, scale):
    """The sigmoid group-limited rule, one row at a time in plain Python."""
    gates, chosen = [], []
    for row in scores:
        size = len(row) // n_group
        groups = [sorted(row[g * size:(g + 1) * size])[-2:]
                  for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -sum(groups[g]))
        kept = set(kept[:topk_group])
        eligible = [(s, e) for e, s in enumerate(row)
                    if e // size in kept]
        top = sorted(eligible, key=lambda p: -p[0])[:top_k]
        total = sum(s for s, _ in top) + 1e-20
        gates.append([s / total * scale for s, _ in top])
        chosen.append([e for _, e in top])
    return np.asarray(gates), np.asarray(chosen)


def test_the_sigmoid_group_limited_router_against_a_plain_python_one(rng):
    logits = (2.0 * rng.normal(size=(40, 24))).astype(np.float32)
    gates, experts = pt.nn.functional.route_top_k(
        jnp.asarray(logits), 4, "sigmoid", n_group=4, topk_group=2,
        scale=2.5)
    want_gates, want_experts = _plain_router(
        1.0 / (1.0 + np.exp(-logits.astype(np.float64))), 4, 4, 2, 2.5)
    np.testing.assert_array_equal(np.sort(experts, -1),
                                  np.sort(want_experts, -1))
    np.testing.assert_allclose(np.sort(gates, -1), np.sort(want_gates, -1),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    # the limit bites: the plain top-4 of all 24 differs on some rows
    _, free = pt.nn.functional.route_top_k(jnp.asarray(logits), 4, "sigmoid")
    assert (np.sort(free, -1) != np.sort(experts, -1)).any()
    # and every chosen expert lies in one of at most two groups of 6
    assert all(len({e // 6 for e in row}) <= 2 for row in np.asarray(experts))


def test_one_group_is_plain_top_k_and_softmax_stays_as_it_was(rng):
    logits = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
    gates, experts = pt.nn.functional.route_top_k(logits, 3, "sigmoid",
                                                  n_group=1, topk_group=1)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    order = np.argsort(-s, axis=-1)[:, :3]
    np.testing.assert_array_equal(experts, order)
    top = np.take_along_axis(s, order, -1)
    np.testing.assert_allclose(gates, top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    # the softmax rule: the same numbers to the bit as the expression it
    # was before the rule became data
    probs = jax.nn.softmax(logits, axis=-1)
    want, want_e = jax.lax.top_k(probs, 3)
    got, got_e = pt.nn.functional.route_top_k(logits, 3)
    np.testing.assert_array_equal(got, want / jnp.sum(want, -1,
                                                      keepdims=True))
    np.testing.assert_array_equal(got_e, want_e)
    with pytest.raises(ValueError, match="scoring"):
        pt.nn.functional.route_top_k(logits, 3, "tanh")


@pytest.mark.parametrize("rows", [8, 200])
def test_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer(
        rows, monkeypatch):
    """192-wide router in miniature: 32 experts in 8 groups of 4, the 4
    best groups, 8 a token, held 2 a share by 16 holders.  The shares'
    routed parts plus the shared expert ONCE add up to the uncut layer,
    which a plain Python loop over tokens and experts gives.  8 rows run
    every held expert on every row, 200 the grouped matmuls (the line
    between the routes brought down to these widths)."""
    from paddle_tpu.core.errors import InvalidArgumentError
    from paddle_tpu.nn.functional import moe

    monkeypatch.setattr(moe, "_EVERY_EXPERT_MACS", 8 * 32 * 16 * 8)

    h, f, e, k = 16, 8, 32, 8
    rng = np.random.default_rng(3)
    x = rng.normal(size=(rows, h)).astype(np.float32)
    whole = pt.nn.SparseExperts(h, f, e, k, scoring="sigmoid", n_group=8,
                                topk_group=4, routed_scale=2.5,
                                shared_size=f, initializer_range=0.5)
    got = np.asarray(whole(pt.to_tensor(x)).value)
    # the uncut layer, plainly
    wr, wg, wu, wd = (np.asarray(p.value, np.float64) for p in (
        whole.router, whole.w_gate, whole.w_up, whole.w_down))
    silu = lambda a: a / (1.0 + np.exp(-a))
    gates, chosen = _plain_router(1.0 / (1.0 + np.exp(-(x @ wr))), k, 8, 4,
                                  2.5)
    sg, su, sd = (np.asarray(p.weight.value, np.float64) for p in (
        whole.shared.gate_proj, whole.shared.up_proj,
        whole.shared.down_proj))
    want = (silu(x @ sg) * (x @ su)) @ sd
    for t in range(rows):
        for g, ex in zip(gates[t], chosen[t]):
            want[t] += g * ((silu(x[t] @ wg[ex]) * (x[t] @ wu[ex])) @ wd[ex])
    assert np.abs(got - want).max() < 2e-4 and np.abs(want).max() > 0.5
    # sixteen holders of two experts each
    parts = np.zeros_like(got)
    for first in range(0, e, 2):
        share = pt.nn.SparseExperts(h, f, e, k, held=(first, 2),
                                    scoring="sigmoid", n_group=8,
                                    topk_group=4, routed_scale=2.5,
                                    shared_size=f)
        share.router._replace_value(whole.router.value)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name)._replace_value(
                getattr(whole, name).value[first:first + 2])
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(share.shared, name).weight._replace_value(
                getattr(whole.shared, name).weight.value)
        routed = np.asarray(share.routed(pt.to_tensor(x)).value)
        parts += routed
        if first == 0:
            shared = np.asarray(share.shared(pt.to_tensor(x)).value)
            # a holder's forward is its routed part and the shared expert
            np.testing.assert_allclose(
                share(pt.to_tensor(x)).value, routed + shared, atol=1e-6)
    assert np.abs(parts + shared - want).max() < 2e-4
    assert np.abs(shared).max() > 0.05 and np.abs(parts).max() > 0.5
    with pytest.raises(InvalidArgumentError, match="n_group"):
        pt.nn.SparseExperts(h, f, e, k, scoring="sigmoid", n_group=8,
                            topk_group=1)        # 4 experts cannot give 8
    with pytest.raises(InvalidArgumentError, match="n_group"):
        pt.nn.SparseExperts(h, f, e, k, n_group=8, topk_group=4)  # softmax
    with pytest.raises(InvalidArgumentError, match="scoring"):
        pt.nn.SparseExperts(h, f, e, k, scoring="tanh")
