"""Compressed convolutional attention (``nn.CCAttention``): q and k mixed
along the sequence by two causal convolutions, values from two tokens, and a
layer that owns TWO cache entries, K/V and a convolution state.

At small widths on the CPU, float32 (width 64, 4 query heads on 2 K/V heads
of 16, taps (2, 2), rotary on half a head; the benchmark's seeded weights of
``toy-cca.json``'s layer 1):

1. the layer against the plain reference's attention
   (``benchmark/harness/cca_reference.py``), and the control ``no_mix``
   moves it;
2. its three paths are one function: no cache, a prompt from position 0
   against a fresh cache, a chunk that starts mid-way and single steps, in
   both K/V layouts, for other taps too;
3. the state's discipline: a padded chunk leaves the state of its true
   length, a closed window leaves state to the bit;
4. what it cannot build is refused by name.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.jit.cache import entry_layout
from paddle_tpu.nn import CCADecodeCache, CCAttention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import cca_reference as ref  # noqa: E402
from harness import cca_weights as cw  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "toy-cca.json")) as _f:
    CFG = json.load(_f)
SEED = 11
WIDTH = (4 + 2) * 16            # the latents u: query heads, then K/V heads


def _layer(taps=(2, 2)):
    pt.seed(0)
    from paddle_tpu.core.dtype import get_default_dtype, set_default_dtype
    was = get_default_dtype()
    set_default_dtype("float32")
    try:
        return CCAttention(64, 4, 2, 16, taps, 10000.0, 8)
    finally:
        set_default_dtype(was)


@pytest.fixture(scope="module")
def made():
    return cw.make_layer(CFG, SEED, 1)


@pytest.fixture(scope="module")
def layer(made):
    m = _layer()
    named = cw.to_program(dict(made))
    for name, p in m.named_parameters():
        p._replace_value(named["self_attn." + name])
    return m


def _inputs(length, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    return pt.to_tensor(jnp.asarray(rng.normal(size=(batch, length, 64)),
                                    jnp.float32))


def _run(layer, a, cuts, layout="paged", max_len=48):
    """The layer over ``a`` in chunks that end at ``cuts``, through a cache;
    ``(outputs [1, L, 64], cache)``."""
    cache = layer.gen_decode_cache(a.shape[0], max_len, "float32",
                                   layout=layout, block_size=8)
    outs, start = [], 0
    for end in cuts:
        o, cache = layer(a[:, start:end], cache=cache)
        outs.append(o.value)
        start = end
    return jnp.concatenate(outs, axis=1), cache


# -- 1. against the reference ----------------------------------------------------

def test_the_layer_agrees_with_the_reference_attention(layer, made):
    a = _inputs(40)
    want = ref.attention(a.value[0], made, jnp.arange(40), cw.sizes(CFG),
                         "float32")
    got = layer(a).value[0]
    # float32 sums in another order, outputs of order 0.3
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(want))) > 0.1
    # the mixing along the sequence matters: the control moves the output
    plain = ref.attention(a.value[0], made, jnp.arange(40), cw.sizes(CFG),
                          "no_mix")
    assert float(jnp.max(jnp.abs(plain - want))) > 0.05


def test_the_parameters_are_the_published_ones(layer):
    shapes = {n: tuple(p.shape) for n, p in layer.named_parameters()}
    assert shapes == {
        "qk_down.weight": (64, WIDTH), "v_proj.weight": (64, 32),
        "o_proj.weight": (64, 64), "conv0_weight": (2, WIDTH),
        "conv0_bias": (WIDTH,), "conv1_weight": (2, 6, 16, 16),
        "conv1_bias": (WIDTH,), "temp": (2,)}
    # at the published widths: 5,575,682 parameters a layer
    big = jax.eval_shape(lambda: [p.value for p in CCAttention(
        2048, 8, 2, 128, (2, 2), 5e6, 64).parameters()])
    assert sum(int(np.prod(p.shape)) for p in big) == 5575682


# -- 2. three paths, one function -------------------------------------------------

@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("cuts", [(40,), (24, 40), (24, 31) + tuple(range(32, 41)),
                                  tuple(range(1, 41))],
                         ids=["prompt", "prompt+chunk", "prompt+chunk+steps",
                              "steps"])
def test_cached_paths_agree_with_the_uncached_forward(layer, layout, cuts):
    a = _inputs(40, seed=1)
    want = layer(a).value
    got, cache = _run(layer, a, cuts, layout)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    kv, state = cache
    assert int(kv.index) == int(state.index) == 40
    # the state is the last position's: its latents, its first
    # convolution's output, its second value half
    u = layer.qk_down(a).value[0]
    assert bool(jnp.allclose(state.u[0], u[-1], atol=1e-6))
    assert bool(jnp.allclose(state.v_next[0],
                             layer.v_proj(a).value[0, -1, 16:], atol=1e-6))
    c0 = layer.conv0_bias.value + layer.conv0_weight.value[0] * u[-2] \
        + layer.conv0_weight.value[1] * u[-1]
    assert bool(jnp.allclose(state.c0[0], c0, atol=1e-5))


def test_a_chunk_known_to_start_at_zero_attends_its_own_keys(layer,
                                                             monkeypatch):
    """What each path calls: a prompt against a fresh cache the causal
    attention over its own keys, every other chunk the cached ops."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    seen = []
    for name in ("causal_attention", "paged_decode_attention",
                 "decode_attention"):
        plain = getattr(fa, name)

        def counted(*args, _plain=plain, _name=name, **kwargs):
            seen.append(_name)
            return _plain(*args, **kwargs)
        monkeypatch.setattr(fa, name, counted)
    a = _inputs(12, seed=2)
    layer(a)
    assert seen == ["causal_attention"]
    seen.clear()
    _run(layer, a, (8, 11, 12), "paged")
    # (the cached ops call one another for grouped heads: what is counted
    # is the prompt's one call and which family ran after it)
    assert seen[0] == "causal_attention" \
        and seen.count("causal_attention") == 1 \
        and "paged_decode_attention" in seen
    seen.clear()
    _run(layer, a, (8, 12), "dense")
    assert seen[0] == "causal_attention" \
        and seen.count("causal_attention") == 1 \
        and "decode_attention" in seen \
        and "paged_decode_attention" not in seen
    # under jit a traced index is not known to be 0: the cached ops
    kv, state = layer.gen_decode_cache(1, 48, "float32", layout="paged",
                                       block_size=8)
    seen.clear()
    jax.jit(lambda x, idx: layer(
        pt.to_tensor(x), cache=(kv._replace(index=idx),
                                state._replace(index=idx)))[0].value)(
        a.value, jnp.zeros((), jnp.int32))
    assert "causal_attention" not in seen


def test_other_taps_keep_longer_states(made):
    m = _layer((3, 4))
    a = _inputs(30, seed=3)
    want = m(a).value
    for cuts in [(30,), (7, 19, 30), tuple(range(1, 31))]:
        got, (_, state) = _run(m, a, cuts)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert state.u.shape == (1, 2 * WIDTH) and state.c0.shape == (1,
                                                                  3 * WIDTH)
    u = m.qk_down(a).value[0]
    assert bool(jnp.allclose(state.u[0], jnp.concatenate([u[-2], u[-1]]),
                             atol=1e-6))
    # and the reference computes the same function of these taps
    p = {k: v for k, v in made.items()}
    p["w0"] = m.conv0_weight.value.T
    p["w1"] = m.conv1_weight.value.transpose(1, 3, 2, 0)
    p["b0"], p["b1"] = m.conv0_bias.value, m.conv1_bias.value.reshape(6, 16)
    p["temp"] = m.temp.value
    p["w_q"], p["w_k"] = jnp.split(m.qk_down.weight.value, [64], axis=1)
    p["w_v1"], p["w_v2"] = jnp.split(m.v_proj.weight.value, 2, axis=1)
    p["w_o"] = m.o_proj.weight.value
    ref_out = ref.attention(a.value[0], p, jnp.arange(30),
                            dict(cw.sizes(CFG), k0=3, k1=4), "float32")
    assert float(jnp.max(jnp.abs(want[0] - ref_out))) < 1e-5


# -- 3. the state's discipline -----------------------------------------------------

@pytest.mark.parametrize("true_len", [1, 2, 13])
def test_a_padded_chunk_leaves_the_state_of_its_true_length(layer, true_len):
    a = _inputs(16, seed=4)
    _, (_, exact) = _run(layer, a[:, :true_len], (true_len,))
    kv, state = layer.gen_decode_cache(1, 48, "float32", layout="paged",
                                       block_size=8)
    _, (_, padded) = layer(a, cache=(kv, state._replace(
        limit=jnp.asarray(true_len, jnp.int32))))
    for f in ("u", "c0", "v_next"):
        assert bool(jnp.all(getattr(padded, f) == getattr(exact, f))), f


def test_a_closed_window_leaves_the_state_to_the_bit(layer):
    """A pool's step over three slots, the middle one free (``limit`` 0 for
    it): its state comes through untouched, the others move."""
    rng = np.random.default_rng(5)
    kv, state = layer.gen_decode_cache(3, 48, "float32", per_slot=True,
                                       layout="paged", block_size=8,
                                       num_blocks=10)
    rnd = lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    state = state._replace(u=rnd(state.u), c0=rnd(state.c0),
                           v_next=rnd(state.v_next),
                           index=jnp.asarray([5, 7, 9], jnp.int32),
                           limit=jnp.asarray([48, 0, 48], jnp.int32))
    kv = kv._replace(index=state.index, table=jnp.asarray(
        [[1, 2, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0], [5, 6, 0, 0, 0, 0]],
        jnp.int32))
    _, (_, new) = layer(_inputs(1, seed=6, batch=3), cache=(kv, state))
    for f in ("u", "c0", "v_next"):
        old, got = getattr(state, f), getattr(new, f)
        assert bool(jnp.all(got[1] == old[1])), f
        assert not bool(jnp.any(got[0] == old[0])), f
        assert not bool(jnp.any(got[2] == old[2])), f
    assert new.index.tolist() == [6, 8, 10]


def test_the_two_entries_are_of_two_kinds(layer):
    kv, state = layer.gen_decode_cache(2, 64, "bfloat16", per_slot=True,
                                       layout="paged", block_size=8,
                                       num_blocks=9)
    assert entry_layout(kv).name == "paged"
    assert entry_layout(state).name == "recurrent"
    assert isinstance(state, CCADecodeCache)
    assert kv.k.shape == (9, 2, 8, 16) and kv.k.dtype == jnp.bfloat16
    # flat, in the layer's own type whatever the K/V's
    assert state.u.shape == state.c0.shape == (2, WIDTH)
    assert state.v_next.shape == (2, 16) and state.u.dtype == jnp.float32
    assert int(state.limit) == 64
    dense, _ = layer.gen_decode_cache(1, 64)
    assert entry_layout(dense).name == "dense"
    assert dense.k.shape == (1, 2, 64, 16)


# -- 4. refusals ---------------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(num_kv_heads=1), "K/V heads are an even number"),
    (dict(num_kv_heads=3, num_heads=6), "K/V heads are an even number"),
    (dict(num_heads=5), "divides the query heads"),
    (dict(rotary_dim=7), "rotary_dim 7 of head_dim 16"),
    (dict(rotary_dim=32), "rotary_dim 32 of head_dim 16"),
    (dict(conv_taps=(1, 2)), "at least 2 taps"),
    (dict(conv_taps=(2,)), "two convolutions"),
])
def test_what_it_cannot_build_is_refused(kwargs, match):
    args = dict(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16)
    with pytest.raises(InvalidArgumentError, match=match):
        CCAttention(**dict(args, **kwargs))


def test_an_int8_cache_is_refused(layer):
    with pytest.raises(InvalidArgumentError, match="float K/V cache"):
        layer.gen_decode_cache(1, 16, "int8")
