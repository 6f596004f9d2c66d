"""The hard-label softmax cross-entropy as one op with its own backward
(``nn/functional/loss.py`` ``_softmax_xent_rows``): against the log-softmax +
gather composition it replaced, kept here as the reference, and what it is
for, counted from the traced program: no float32 array of the logits' shape
is kept for the backward, no gather, no scatter, and no black-list cast in
front of it under ``auto_cast``."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import TransformerLMCriterion
from paddle_tpu.nn.functional import loss as L

V = 37          # classes: odd, so no tile hides an edge


def composition(input, label, weight=None, ignore_index=-100,
                reduction="mean", axis=-1, use_softmax=True,
                soft_label=False, label_smoothing=0.0):
    """``cross_entropy`` as it stood before the fused core, letter for
    letter (parent of PR 38)."""
    if use_softmax:
        logp = jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.clip(input, 1e-10, 1.0))
    if soft_label or (label.ndim == input.ndim
                      and label.shape == input.shape):
        soft = label
        if label_smoothing > 0.0:
            n = input.shape[axis]
            soft = soft * (1.0 - label_smoothing) + label_smoothing / n
        loss = -jnp.sum(soft * logp, axis=axis)
        valid = None
    else:
        lbl = label
        if lbl.ndim == input.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        lbl = lbl.astype(jnp.int32)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis),
                                     axis=axis)
        loss = -jnp.squeeze(picked, axis=axis)
        if label_smoothing > 0.0:
            smooth_loss = -jnp.mean(logp, axis=axis)
            loss = (1.0 - label_smoothing) * loss \
                + label_smoothing * smooth_loss
        if weight is not None:
            loss = loss * weight[safe]
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            if weight is not None:
                denom = jnp.sum(jnp.where(valid, weight[safe], 0.0))
            else:
                denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
            return jnp.sum(loss) / denom
    return {"mean": jnp.mean, "sum": jnp.sum,
            "none": lambda v: v}[reduction](loss)


def case(ndim, axis, ignored, seed=0):
    """Logits (float32, a few units wide so the softmax is not flat) with
    the classes on ``axis``, and labels with none / some / all ignored."""
    rng = np.random.default_rng([seed, ndim, axis % ndim])
    rows = (6, 5) if ndim == 3 else (30,)
    shape = list(rows)
    shape.insert(axis % ndim, V)
    x = jnp.asarray(rng.normal(size=shape) * 3.0, jnp.float32)
    lbl = rng.integers(0, V, rows)
    drop = {"none": np.zeros(rows, bool), "some": rng.random(rows) < 0.4,
            "all": np.ones(rows, bool)}[ignored]
    return x, jnp.asarray(np.where(drop, -100, lbl), jnp.int32)


def weights(given):
    if not given:
        return None
    return jnp.asarray(np.random.default_rng(7).uniform(0.5, 2.0, V),
                       jnp.float32)


def scalar(loss, cot):
    """A scalar of any reduction's result: ``none`` is contracted with a
    fixed cotangent so every row's gradient is exercised."""
    return jnp.sum(loss * cot) if loss.ndim else loss


def ulps_bf16(a, b):
    """Distance of two bfloat16 arrays in units in the last place."""
    def key(v):
        bits = np.asarray(v).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, 0x8000 - bits, bits)
    return np.abs(key(a) - key(b))


GRID = list(itertools.product(
    ("mean", "sum", "none"), ("none", "some", "all"), (False, True),
    ((2, -1), (3, -1), (3, 1))))


@pytest.mark.parametrize("reduction,ignored,weighted,shape", GRID)
def test_float32_logits_agree_to_round_off(reduction, ignored, weighted,
                                           shape):
    ndim, axis = shape
    x, lbl = case(ndim, axis, ignored)
    w = weights(weighted)
    cot = jnp.asarray(np.random.default_rng(3).normal(size=lbl.shape),
                      jnp.float32)

    def run(fn):
        return jax.value_and_grad(lambda v: scalar(
            fn(v, lbl, weight=w, reduction=reduction, axis=axis), cot))(x)

    got, dgot = run(L.cross_entropy)
    assert got.dtype == jnp.float32 and dgot.dtype == jnp.float32
    if ignored == "all":
        # a batch with nothing to predict: zero, not 0 / 0 (the weighted
        # mean of the composition read NaN there)
        assert float(jnp.sum(jnp.abs(got))) == 0.0
        assert float(jnp.sum(jnp.abs(dgot))) == 0.0
        return
    ref, dref = run(composition)
    np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(dgot, dref, rtol=1e-5, atol=2e-7)


@pytest.mark.parametrize("reduction,ignored,weighted,shape", GRID)
def test_bfloat16_logits_agree_with_the_up_cast_composition(
        reduction, ignored, weighted, shape):
    """What autocast's black list computed: the composition on
    float32(bfloat16 logits), its gradient cast back to bfloat16."""
    ndim, axis = shape
    x, lbl = case(ndim, axis, ignored, seed=1)
    x = x.astype(jnp.bfloat16)
    w = weights(weighted)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=lbl.shape),
                      jnp.float32)
    got, dgot = jax.value_and_grad(lambda v: scalar(L.cross_entropy(
        v, lbl, weight=w, reduction=reduction, axis=axis), cot))(x)
    ref, dref = jax.value_and_grad(lambda v: scalar(composition(
        v.astype(jnp.float32), lbl, weight=w, reduction=reduction,
        axis=axis), cot))(x)
    assert got.dtype == jnp.float32          # as under the black list
    assert dgot.dtype == dref.dtype == jnp.bfloat16
    if ignored == "all":
        assert float(jnp.sum(jnp.abs(got))) == 0.0
        assert not np.asarray(dgot.astype(jnp.float32)).any()
        return
    np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
    assert int(ulps_bf16(dgot, dref).max()) <= 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_eager_tensors_and_the_tape(dtype, reduction):
    x, lbl = case(2, -1, "some", seed=2)
    x = x.astype(dtype)
    t = Tensor(x, stop_gradient=False)
    loss = F.cross_entropy(t, Tensor(lbl), reduction=reduction)
    assert isinstance(loss, Tensor) and loss.dtype == jnp.float32
    loss.sum().backward()
    ref, dref = jax.value_and_grad(lambda v: jnp.sum(composition(
        v.astype(jnp.float32), lbl, reduction=reduction)))(x)
    np.testing.assert_allclose(np.asarray(loss.sum().value), ref, rtol=2e-6)
    assert t.grad.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(t.grad.value.astype(jnp.float32)),
        np.asarray(dref.astype(jnp.float32)), rtol=1e-2, atol=2e-7)


@pytest.mark.parametrize("ignored", ["some", "all"])
def test_layer_and_fused_op_go_through_the_same_core(ignored):
    x, lbl = case(2, -1, ignored, seed=5)
    w = weights(True)
    layer = pt.nn.CrossEntropyLoss(weight=Tensor(w), reduction="sum")
    np.testing.assert_allclose(
        np.asarray(layer(Tensor(x), Tensor(lbl)).value),
        composition(x, lbl, weight=w, reduction="sum"), rtol=2e-6)
    rows = F.softmax_with_cross_entropy(Tensor(x), Tensor(lbl[:, None]))
    assert rows.shape == [x.shape[0], 1]
    np.testing.assert_allclose(
        np.asarray(rows.value)[:, 0], composition(x, lbl, reduction="none"),
        rtol=2e-6, atol=2e-6)
    loss, soft = F.softmax_with_cross_entropy(Tensor(x), Tensor(lbl),
                                              return_softmax=True)
    np.testing.assert_array_equal(np.asarray(soft.value),
                                  np.asarray(jax.nn.softmax(x, axis=-1)))


@pytest.mark.parametrize("shift", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_language_model_criterion(shift, dtype):
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=(3, 8, V)) * 2.0, dtype)
    labels = jnp.asarray(np.where(rng.random((3, 8)) < 0.5, -100,
                                  rng.integers(0, V, (3, 8))), jnp.int32)
    criterion = TransformerLMCriterion(shift_labels=shift)

    def ours(v):
        return criterion(Tensor(v), Tensor(labels)).value

    def ref(v):
        lg, lb = (v[:, :-1], labels[:, 1:]) if shift else (v, labels)
        return composition(lg.reshape(-1, V).astype(jnp.float32),
                           lb.reshape(-1))

    got, dgot = jax.value_and_grad(ours)(logits)
    want, dwant = jax.value_and_grad(ref)(logits)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert dgot.dtype == logits.dtype
    if dtype == "bfloat16":
        assert int(ulps_bf16(dgot, dwant).max()) <= 1
    else:
        np.testing.assert_allclose(dgot, dwant, rtol=1e-5, atol=2e-7)
    if shift:       # the last position predicts nothing
        assert not np.asarray(dgot[:, -1].astype(jnp.float32)).any()


class _Head(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.proj = pt.nn.Linear(8, V)

    def forward(self, x):
        return self.proj(x)


@pytest.mark.parametrize("reduction", ["mean", "sum"])
@pytest.mark.parametrize("autocast", [False, True])
def test_under_train_step_the_parameters_move_as_with_the_composition(
        reduction, autocast, monkeypatch):
    rng = np.random.default_rng(13)
    xs = rng.normal(size=(24, 8)).astype(np.float32)
    ys = np.where(rng.random(24) < 0.3, -100,
                  rng.integers(0, V, 24)).astype(np.int32)

    def train(fused):
        if not fused:       # the op as it was, cast in front of it and all
            monkeypatch.setattr(L, "_takes_fused_core",
                                lambda *a, **k: False)
            monkeypatch.setattr(L.cross_entropy, "amp_upcasts_inside",
                                lambda *a, **k: False)
        pt.seed(5)
        model = _Head()
        opt = pt.optimizer.SGD(0.05, parameters=model.parameters())

        def loss_fn(m, x, y):
            with pt.amp.auto_cast(enable=autocast, dtype="bfloat16"):
                return F.cross_entropy(m(x), y, reduction=reduction)

        step = TrainStep(model, loss_fn, opt, donate=False)
        losses = [float(step(pt.to_tensor(xs), pt.to_tensor(ys)))
                  for _ in range(3)]
        monkeypatch.undo()
        return losses, [np.asarray(p.value) for p in model.parameters()]

    got, params = train(True)
    want, ref_params = train(False)
    # under autocast dx is bfloat16 and may sit one ulp apart
    rtol, atol = (2e-2, 5e-3) if autocast else (1e-5, 1e-6)
    np.testing.assert_allclose(got, want, rtol=rtol)
    assert got[-1] < got[0]
    for a, b in zip(params, ref_params):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


# -- what the change is for, counted without a chip -------------------------

N_ROWS, N_CLASSES = 64, 4096


def big_case():
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(N_ROWS, N_CLASSES)), jnp.bfloat16)
    lbl = jnp.asarray(np.where(rng.random(N_ROWS) < 0.85, -100,
                               rng.integers(0, N_CLASSES, N_ROWS)),
                      jnp.int32)
    return x, lbl


def primitives(jaxpr, out=None):
    """Every primitive's name in a jaxpr, inner jaxprs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    primitives(inner, out)
    return out


def kept_for_backward(fn, x):
    """Shapes and types of what the forward keeps for the backward: the
    leaves of ``jax.vjp``'s pullback."""
    _, pullback = jax.eval_shape(lambda v: jax.vjp(fn, v), x)
    return [(tuple(l.shape), jnp.dtype(l.dtype))
            for l in jax.tree.leaves(pullback)]


def criterion_loss(x, lbl):
    return TransformerLMCriterion(shift_labels=False)(
        Tensor(x[None]), Tensor(lbl[None])).value


def test_no_float32_copy_of_the_logits_is_kept_for_the_backward():
    x, lbl = big_case()
    wide = ((N_ROWS, N_CLASSES), jnp.dtype(jnp.float32))
    kept = kept_for_backward(lambda v: criterion_loss(v, lbl), x)
    assert wide not in kept and ((1, N_ROWS, N_CLASSES), wide[1]) not in kept
    assert sum(np.prod(s) * d.itemsize for s, d in kept) \
        <= x.size * 2 + 64 * N_ROWS       # the logits as stored, and rows
    # the same count does see the composition's float32 log-softmax
    old = kept_for_backward(
        lambda v: composition(v.astype(jnp.float32), lbl), x)
    assert wide in old
    # and the forward hands on one float32 number, not an array
    out = jax.eval_shape(lambda v: criterion_loss(v, lbl), x)
    assert out.shape == () and out.dtype == jnp.float32


def test_no_gather_and_no_scatter_forward_or_backward():
    x, lbl = big_case()
    names = primitives(jax.make_jaxpr(jax.value_and_grad(
        lambda v: criterion_loss(v, lbl)))(x).jaxpr)
    assert not [n for n in names if "gather" in n or "scatter" in n]
    old = primitives(jax.make_jaxpr(jax.value_and_grad(
        lambda v: composition(v.astype(jnp.float32), lbl)))(x).jaxpr)
    assert "gather" in old and any("scatter" in n for n in old)


def test_autocast_puts_no_cast_in_front_of_the_op():
    x, lbl = big_case()

    def under_autocast(fn):
        def run(v):
            with pt.amp.auto_cast(level="O1", dtype="bfloat16"):
                return fn(v)
        return jax.make_jaxpr(run)(x).jaxpr

    jaxpr = under_autocast(lambda v: F.cross_entropy(v, lbl))
    core = [e for e in jaxpr.eqns if e.primitive.name.startswith(
        "custom_vjp_call")]
    assert len(core) == 1
    assert [v.aval.dtype for v in core[0].invars
            if getattr(v.aval, "shape", ()) == x.shape] == [jnp.bfloat16]
    assert not [e for e in jaxpr.eqns
                if e.primitive.name == "convert_element_type"
                and e.outvars[0].aval.shape == x.shape]
    # the loss is float32 all the same, as the black list promises
    assert jaxpr.outvars[0].aval.dtype == jnp.float32
    # a path that keeps the composition still gets its cast
    soft = jax.nn.one_hot(jnp.maximum(lbl, 0), N_CLASSES, dtype=jnp.bfloat16)
    kept = under_autocast(lambda v: F.cross_entropy(v, soft,
                                                    soft_label=True))
    assert kept.eqns[0].primitive.name == "convert_element_type" or any(
        e.primitive.name == "convert_element_type"
        and e.outvars[0].aval.shape == x.shape
        and e.outvars[0].aval.dtype == jnp.float32 for e in kept.eqns)


@pytest.mark.parametrize("kwargs", [
    dict(soft_label=True), dict(soft_label=True, label_smoothing=0.1),
    dict(label_smoothing=0.1), dict(label_smoothing=0.1, weighted=True),
    dict(use_softmax=False), dict(use_softmax=False, reduction="none"),
], ids=lambda k: "-".join(sorted(k)))
def test_paths_off_the_core_give_what_they_gave_bit_for_bit(kwargs):
    kwargs = dict(kwargs)
    x, lbl = case(2, -1, "some", seed=19)
    if kwargs.pop("weighted", False):
        kwargs["weight"] = weights(True)
    label = lbl
    if kwargs.get("soft_label"):
        label = jax.nn.softmax(jnp.asarray(
            np.random.default_rng(23).normal(size=x.shape), jnp.float32))
    if kwargs.get("use_softmax") is False:
        x = jax.nn.softmax(x)

    def run(fn):
        return jax.value_and_grad(
            lambda v: jnp.sum(fn(v, label, **kwargs)))(x)

    got, dgot = run(L.cross_entropy)
    want, dwant = run(composition)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dgot), np.asarray(dwant))
    assert not L._takes_fused_core(x, label, **kwargs)
