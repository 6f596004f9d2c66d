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


@pytest.mark.parametrize("rows,route", [(8, "every"), (200, "grouped"),
                                        (8, "touched")])
def test_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer(
        rows, route, monkeypatch):
    """192-wide router in miniature: 32 experts in 8 groups of 4, the 4
    best groups, 8 a token, held 2 a share by 16 holders.  The shares'
    routed parts plus the shared expert ONCE add up to the uncut layer,
    which a plain Python loop over tokens and experts gives.  8 rows run
    every held expert on every row, or only the experts some row chose
    where a skipped read is given a price of nothing; 200 the grouped
    matmuls (the lines between the routes brought down to these
    widths)."""
    from paddle_tpu.core.errors import InvalidArgumentError
    from paddle_tpu.nn.functional import moe

    monkeypatch.setattr(moe, "_EVERY_EXPERT_MACS", 8 * 32 * 16 * 8)
    if route == "touched":
        monkeypatch.setattr(moe, "_SKIP_COST_S", 0.0)

    h, f, e, k = 16, 8, 32, 8
    assert moe.expert_route(rows, e, e, k, h, f, 4) == route \
        == moe.expert_route(rows, 2, e, k, h, f, 4)
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


# -- the route between the expert layer's three forms ---------------------------
# (``F.expert_route``: a function of the shapes alone; ``_touched`` runs the
# held experts that some row chose)

def _parent_route(rows, held, experts, top_k, width, size, itemsize=2):
    """The rule before there was a third route (PR 40)."""
    return "every" if rows * held * width * size <= 512 * 128 * 2048 * 768 \
        else "grouped"


# rows, held, experts, top_k, width, an expert's size: the cells' steps
SDAR, ZAYA, AXK1 = ((128, 128, 8, 2048, 768), (16, 16, 1, 2048, 2048),
                    (12, 192, 8, 7168, 2048))
SHAPES = {
    "sdar-block-step": (128,) + SDAR, "zaya-decode": (64,) + ZAYA,
    "axk1-decode": (32,) + AXK1, "axk1-verify-chunk": (256,) + AXK1,
    **{"sdar-prefill-%d" % n: (n,) + SDAR for n in (512, 1024, 2048)},
    **{"zaya-prefill-%d" % n: (n,) + ZAYA for n in (256, 512, 1024)},
    **{"axk1-prefill-%d" % n: (n,) + AXK1 for n in (2048, 4096, 8192)},
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_rule_picks_the_touched_route_at_axk1s_decode_step_alone(shape):
    """At every other shape a cell runs, prefills among them, the route is
    the one the parent's rule gave."""
    from paddle_tpu.nn.functional import moe

    got = moe.expert_route(*SHAPES[shape], 2)
    if shape == "axk1-decode":
        assert got == "touched" and _parent_route(*SHAPES[shape]) == "every"
    else:
        assert got == _parent_route(*SHAPES[shape])
    # the expected saving an expert against the one constant
    rows, held, experts, top_k, width, size = SHAPES[shape]
    saved = (1 - top_k / experts) ** rows * 3 * width * size * 2 / 819e9
    assert (got == "touched") == (saved > moe._SKIP_COST_S
                                  and got != "grouped")
    assert moe.touched_share(rows, experts, top_k) == pytest.approx(
        1 - (1 - top_k / experts) ** rows)


def test_axk1s_rows_are_expected_to_touch_nine_of_the_twelve():
    from paddle_tpu.nn.functional import moe

    assert 12 * moe.touched_share(32, 192, 8) == pytest.approx(8.93, abs=0.01)
    assert moe.touched_share(0, 192, 8) == 0.0


def _parents_every_expert(x, scores, w_gate, w_up, w_down, top_k,
                          renormalise):
    """``sparse_experts`` as the parent traced it under the multiply-add
    line, written out (PR 40-42)."""
    from paddle_tpu.nn.functional import moe

    xt = x.reshape(-1, x.shape[-1])
    held = w_gate.shape[0]
    gates, experts = moe.route_top_k(
        scores.reshape(-1, scores.shape[-1]), top_k, "softmax", 1, 1, 1.0,
        **({} if renormalise else {"renormalise": False}))
    local = experts.reshape(-1) - 0
    key = jnp.where((local >= 0) & (local < held), local, held)
    rows = xt.shape[0]
    gate_of = jnp.sum(
        jnp.where(key.reshape(rows, top_k, 1) == jnp.arange(held),
                  gates[..., None], 0.0), axis=1)
    act = jax.nn.silu(jnp.einsum("th,ehf->tef", xt, w_gate)) \
        * jnp.einsum("th,ehf->tef", xt, w_up)
    act = (act.astype(jnp.float32) * gate_of[..., None]).astype(xt.dtype)
    out = jnp.einsum("tef,efh->th", act, w_down,
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype).reshape(x.shape)


@pytest.mark.parametrize("cell", ["sdar-block-step", "zaya-decode"])
def test_the_other_cells_expert_layers_trace_the_parents_program(cell):
    """Equation for equation, at the cells' own shapes in bfloat16 (shapes
    alone: nothing is allocated)."""
    rows, held, experts, top_k, width, size = SHAPES[cell]
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((rows, width), bf), ((rows, experts), jnp.float32),
        ((held, width, size), bf), ((held, width, size), bf),
        ((held, size, width), bf))]
    renormalise = cell != "zaya-decode"
    got = jax.make_jaxpr(lambda *a: pt.nn.functional.sparse_experts(
        *a, top_k=top_k, renormalise=renormalise))(*args)
    want = jax.make_jaxpr(lambda *a: _parents_every_expert(
        *a, top_k, renormalise))(*args)
    assert str(got) == str(want)
    assert "while" not in str(got)


def _touch_case(touch):
    """12 rows over 8 experts, 2 a token, a share that holds experts 4, 5
    and 6: rows that choose none of the three, all of them, or 4 and 6."""
    rng = np.random.default_rng(5)
    pairs = {"none": [(0, 1), (2, 3), (7, 0)],
             "all": [(4, 5), (5, 6), (6, 4)],
             "some": [(4, 0), (6, 4), (1, 2)]}[touch]
    scores = rng.normal(size=(12, 8)).astype(np.float32)
    for t in range(12):
        scores[t, list(pairs[t % 3])] += 20.0
    x = rng.normal(size=(12, 32)).astype(np.float32)
    mats = [(0.3 * rng.normal(size=s)).astype(np.float32)
            for s in [(3, 32, 16), (3, 32, 16), (3, 16, 32)]]
    return x, scores, mats


@pytest.mark.parametrize("touch", ["none", "all", "some"])
def test_the_touched_route_reads_what_its_rows_chose(touch, monkeypatch):
    """Rows that touch no held expert give exact zeros; rows that touch
    every held expert, or some, what every expert on every row gives."""
    from paddle_tpu.nn.functional import moe

    x, scores, mats = _touch_case(touch)
    call = lambda: np.asarray(jax.jit(
        lambda *a: moe.sparse_experts(*a, top_k=2, first_expert=4))(
            x, scores, *mats))
    assert moe.expert_route(12, 3, 8, 2, 32, 16, 4) == "every"
    every = call()
    monkeypatch.setattr(moe, "_SKIP_COST_S", 0.0)
    assert moe.expert_route(12, 3, 8, 2, 32, 16, 4) == "touched"
    touched = call()
    text = str(jax.make_jaxpr(lambda *a: moe.sparse_experts(
        *a, top_k=2, first_expert=4))(x, scores, *mats))
    assert "while" in text and "ragged_dot" not in text
    if touch == "none":
        assert not touched.any() and not every.any()
    else:
        # float32 sums in another order, values of order 1
        assert np.abs(touched - every).max() < 1e-5
        assert np.abs(every).max() > 0.5
    # the rows that chose nothing held are zeros to the bit in both
    chose = np.isin(np.argsort(-scores, -1)[:, :2], [4, 5, 6]).any(-1)
    assert not touched[~chose].any() and chose.any() == (touch != "none")


def test_a_gradient_through_the_touched_route_fails_by_name(monkeypatch):
    """The loop's trip count is data: nothing differentiates through it,
    and jax says so where the layer is traced (``nn.SparseExperts`` stops
    the gradient at its output)."""
    from paddle_tpu.nn.functional import moe

    x, scores, mats = _touch_case("some")
    loss = lambda x, scores: moe.sparse_experts(
        x, scores, *mats, top_k=2, first_expert=4).sum()
    every = jax.jit(jax.grad(loss))(x, scores)
    assert np.isfinite(np.asarray(every)).all() and np.asarray(every).any()
    monkeypatch.setattr(moe, "_SKIP_COST_S", 0.0)
    with pytest.raises(ValueError, match="[Rr]everse-mode"):
        jax.jit(jax.grad(loss))(x, scores)


def test_the_bench_tool_forces_the_touched_count_and_rehearses_here(capsys):
    """``tools/expert_route_bench.py``: the keys it hands a route touch
    exactly the experts asked for, with the pairs the cell's routing sends
    this share; off the chip it rehearses its control flow and prints no
    time."""
    import json

    from tools import expert_route_bench as bench

    for name, (rows, held, experts, k, _, _) in bench.GEOMETRIES.items():
        counts = bench.touched_counts(rows, held, experts, k)
        assert counts["one"] == 1 and counts["all"] == held
        assert counts["draw"] == {"axk1": 9, "zaya": 16, "sdar": 128}[name]
        for touched in counts.values():
            key = bench.keys_for(touched, rows, held, experts, k)
            assert key.shape == (rows * k,)
            mine = key[key < held]
            assert sorted(set(mine)) == list(range(touched))
            assert len(mine) == max(touched, rows * k * held // experts)
    assert bench.main(["--cpu-toy", "--geometry", "axk1", "--touched",
                       "half"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert [l["route"] for l in lines] == ["every", "touched", "grouped"]
    assert all(l["geometry"] == "toy" and l["touched"] == 2
               and "ms_a_call" not in l and l["max_abs_diff"] < 1e-5
               for l in lines)
    assert bench.main(["--geometry", "axk1"]) == 1      # no chip, no time
