"""Latent attention (``nn.LatentAttention``, ``ops.flash_attention
.latent_decode_attention``, ``ops.pallas_decode
.latent_decode_attention_kernel``) at small widths on the CPU, float32:

1. YaRN's frequencies and ``m`` against numbers worked by hand;
2. the absorbed form equals the expanded form, and which runs (a chunk
   known to start at 0 expanded, every other absorbed, also one longer than
   the kernel's chunk that starts mid-way); a prompt and then steps
   through a latent cache by slot and a paged one agree with the plain
   reference's full forward (``benchmark/harness/latent_reference.py``);
3. the kernel under the interpreter against the composition, with dead
   table entries, a verify chunk, rows that see nothing, a row's last live
   entry at every offset of a grid step's tile, and the rule for the
   tile's width;
4. every refusal by name.
"""
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.errors import InvalidArgumentError
from paddle_tpu.nn import (LatentAttention, LatentDecodeCache,
                           PagedLatentDecodeCache)
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas_decode as pd

# (``paddle_tpu.ops.flash_attention`` the attribute is the function)
fa = importlib.import_module("paddle_tpu.ops.flash_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import latent_reference as ref  # noqa: E402

SCALING = {"factor": 32, "original_max_position_embeddings": 4096,
           "beta_fast": 32, "beta_slow": 1, "mscale": 1,
           "mscale_all_dim": 1, "type": "yarn"}


# -- 1. YaRN -------------------------------------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    """64 rotary channels, theta 10000, factor 32 over 4096 positions.
    Pair ``i`` turns ``4096 theta_i / 2 pi`` times in 4096 positions; the
    pair that turns 32 times is number ``64 ln(4096 / (32 x 2 pi)) / (2 ln
    10000) = 10.47`` and the one that turns once ``22.51``: pairs 0-10
    keep ``theta_i``, pairs 23-31 take ``theta_i / 32``, pair 16 is (16 -
    10) / 13 of the way."""
    inv = F.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000))
    assert (round(low, 2), round(high, 2)) == (10.47, 22.51)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    ramp = 6 / 13
    np.testing.assert_allclose(
        inv[16], plain[16] / 32 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    # the reference computes the same, on its own
    sizes = {"rope": 64, "rope_theta": 10000.0, "rope_factor": 32.0,
             "rope_original": 4096, "beta_fast": 32.0, "beta_slow": 1.0}
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(sizes)), inv,
                               rtol=1e-6)
    # no scaling: the plain frequencies
    np.testing.assert_allclose(F.yarn_inv_freq(64, 10000.0, 1.0, 1), plain,
                               rtol=1e-6)


def test_the_softmax_scale_carries_m_squared():
    assert F.yarn_mscale(32, 1) == pytest.approx(1.34657, abs=1e-5)
    assert F.yarn_mscale(1, 1) == 1.0 and F.yarn_mscale(32, 0) == 1.0
    layer = LatentAttention(64, 4, 32, 128, 128, 64, 128,
                            rope_scaling=SCALING)
    assert layer.sm_scale == pytest.approx(192 ** -0.5 * 1.34657 ** 2,
                                           rel=1e-5)
    assert layer.rope_scale == 1.0          # mscale(32, 1) / mscale(32, 1)
    assert layer.entry_width == 256         # 128 + 64, whole 128-lane tiles
    assert LatentAttention(64, 4, 32, 512, 128, 64, 128).entry_width == 640
    plain = LatentAttention(64, 4, 32, 128, 128, 64, 128)
    assert plain.sm_scale == pytest.approx(192 ** -0.5)


def test_rotary_pairs_turn_interleaved_channels():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 5, 8)),
                    jnp.float32)
    inv = np.asarray([1.0, 0.5, 0.25, 0.125], np.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    got = np.asarray(F.rotary_embedding_pairs(x, pos, inv))
    b, h, t, i = 1, 2, 3, 1
    ang = float(pos[b, t]) * inv[i]
    x1, x2 = float(x[b, h, t, 2 * i]), float(x[b, h, t, 2 * i + 1])
    assert got[b, h, t, 2 * i] == pytest.approx(
        x1 * math.cos(ang) - x2 * math.sin(ang), abs=1e-5)
    assert got[b, h, t, 2 * i + 1] == pytest.approx(
        x2 * math.cos(ang) + x1 * math.sin(ang), abs=1e-5)
    # position 0 turns nothing; a shared [L] vector serves every row
    np.testing.assert_allclose(got[0, :, 0], np.asarray(x[0, :, 0]),
                               atol=1e-6)
    same = np.asarray(F.rotary_embedding_pairs(x, pos[0], inv))
    np.testing.assert_allclose(same[0], got[0], atol=1e-6)


# -- 2. the two forms, through the caches --------------------------------------

@pytest.fixture(scope="module")
def layer():
    pt.seed(3)
    layer = LatentAttention(64, 4, 32, 128, 16, 8, 16,
                            rope_scaling=dict(
                                SCALING,
                                original_max_position_embeddings=16))
    layer.eval()
    for p in layer.parameters():      # 0.02 would leave every score at 0
        if len(p.shape) == 2:
            p._replace_value(p.value * 3)
    return layer


def _x(rows=2, length=24, seed=0):
    return pt.to_tensor(jnp.asarray(
        np.random.default_rng(seed).normal(size=(rows, length, 64)),
        jnp.float32))


def _reference(layer, x):
    """The plain reference's attention, its weights read from the layer."""
    p = {"w_dq": layer.q_down.weight.value, "q_norm": layer.q_norm.weight.value,
         "w_uq": layer.q_up.weight.value, "w_dkv": layer.kv_down.weight.value,
         "kv_norm": layer.kv_norm.weight.value,
         "w_ukv": layer.kv_up.weight.value, "w_o": layer.o_proj.weight.value}
    sizes = {"num_heads": 4, "nope": 16, "rope": 8, "v_dim": 16,
             "kv_rank": 128, "norm_eps": 1e-6, "rope_theta": 10000.0,
             "rope_factor": 32.0, "rope_original": 16, "beta_fast": 32.0,
             "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0}
    return jnp.stack([ref.attention(row, p, jnp.arange(row.shape[0]), sizes,
                                    "float32") for row in x.value])


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_a_prompt_then_steps_agree_with_the_references_full_forward(layer,
                                                                    layout):
    x = _x()
    want = _reference(layer, x)
    np.testing.assert_allclose(layer(x).value, want, atol=1e-4)
    cache = layer.gen_decode_cache(2, 32, "float32", layout=layout,
                                   block_size=8)
    assert isinstance(cache, LatentDecodeCache if layout == "dense"
                      else PagedLatentDecodeCache)
    out, cache = layer(x[:, :16], cache=cache)      # expanded: a prompt
    got = [out.value]
    for t in range(16, 24):                         # absorbed: steps
        out, cache = layer(x[:, t:t + 1], cache=cache)
        got.append(out.value)
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want,
                               atol=1e-4)
    assert int(cache.index) == 24
    # what a position keeps: its latent, its rotary key, zeros
    entry = np.asarray(cache.latent).reshape(-1, 256)
    assert np.abs(entry[:, 136:]).max() == 0.0
    assert np.abs(entry[:, :136]).max() > 0.1


def _calls(monkeypatch):
    """Which of the two attention paths ran, counted."""
    seen = {"expanded": 0, "absorbed": 0}
    for name, key in (("causal_attention", "expanded"),
                      ("latent_decode_attention", "absorbed")):
        plain = getattr(fa, name)

        def counted(*args, _plain=plain, _key=key, **kwargs):
            seen[_key] += 1
            return _plain(*args, **kwargs)
        monkeypatch.setattr(fa, name, counted)
    return seen


def _jitted(layer):
    """The layer under ``jax.jit`` with the cache an argument: its index is
    traced, so nothing is known of where the chunk starts."""
    return jax.jit(lambda v, cache: (lambda out: (out[0].value, out[1]))(
        layer(pt.to_tensor(v), cache=cache)))


@pytest.mark.parametrize("length", [1, 8, 9])
def test_the_absorbed_form_equals_the_expanded_form(layer, monkeypatch,
                                                    length):
    """A chunk against a cache KNOWN to stand at 0 runs expanded (a
    prompt); the same chunk against a cache whose index is traced runs
    absorbed, whatever its length: the same function."""
    seen = _calls(monkeypatch)
    x = _x(length=9, seed=4)
    want = layer(x).value
    assert seen == {"expanded": 1, "absorbed": 0}
    cache = layer.gen_decode_cache(2, 16, "float32", per_slot=True)
    out, _ = layer(x[:, :length], cache=cache)
    assert seen == {"expanded": 2, "absorbed": 0}
    np.testing.assert_allclose(out.value, want[:, :length], atol=1e-4)
    got, after = _jitted(layer)(x.value[:, :length], cache)
    assert seen == {"expanded": 2, "absorbed": 1}
    np.testing.assert_allclose(got, want[:, :length], atol=1e-4)
    np.testing.assert_array_equal(after.index, [length, length])


def test_the_up_projections_are_views_of_one_weight(layer):
    # W_UK and W_UV are views of kv_up's one weight
    w_uk, w_uv = layer._kv_up_views()
    w = np.asarray(layer.kv_up.weight.value).reshape(128, 4, 32)
    np.testing.assert_array_equal(w_uk, w[..., :16])
    np.testing.assert_array_equal(w_uv, w[..., 16:])
    assert sum(int(np.prod(p.shape)) for p in layer.parameters()) \
        == 64 * 32 + 32 + 32 * 96 + 64 * 136 + 128 + 128 * 128 + 64 * 64


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("per_slot", [False, True])
def test_a_long_chunk_that_starts_mid_way_sees_its_cached_context(
        layer, monkeypatch, layout, per_slot):
    """Nine positions (more than the kernel's chunk) against a cache that
    stands at 7: absorbed through the composition, in eager code (the
    index is concrete and not 0) and under ``jit`` (it is traced), never
    the prompt form over the chunk alone."""
    seen = _calls(monkeypatch)
    x = _x(length=16, seed=6)
    want = layer(x).value
    cache = layer.gen_decode_cache(2, 16, "float32", per_slot=per_slot,
                                   layout=layout, block_size=8)
    _, cache = layer(x[:, :7], cache=cache)
    assert seen == {"expanded": 2, "absorbed": 0}
    out, after = layer(x[:, 7:], cache=cache)
    assert seen == {"expanded": 2, "absorbed": 1}
    np.testing.assert_allclose(out.value, want[:, 7:], atol=1e-4)
    got, after = _jitted(layer)(x.value[:, 7:], cache)
    assert seen == {"expanded": 2, "absorbed": 2}
    np.testing.assert_allclose(got, want[:, 7:], atol=1e-4)
    assert np.asarray(after.index).max() == 16


def test_slots_step_at_positions_of_their_own(layer):
    x = _x(rows=3, length=20, seed=5)
    want = layer(x).value
    cache = layer.gen_decode_cache(3, 24, "float32", per_slot=True,
                                   layout="paged", block_size=8)
    starts = [12, 5, 19]
    for b, n in enumerate(starts):                  # a prompt a row
        row = layer.gen_decode_cache(1, 24, "float32", layout="paged",
                                     block_size=8)
        _, row = layer(x[b:b + 1, :n], cache=row)
        cache = cache._replace(
            latent=cache.latent.at[1 + 3 * b:4 + 3 * b].set(row.latent[1:]),
            index=cache.index.at[b].set(n))
    step = jnp.stack([x.value[b, n] for b, n in enumerate(starts)])[:, None]
    out, cache = layer(pt.to_tensor(step), cache=cache)
    for b, n in enumerate(starts):
        np.testing.assert_allclose(out.value[b, 0], want[b, n], atol=1e-4)
    np.testing.assert_array_equal(cache.index, [13, 6, 20])


# -- 3. the kernel under the interpreter ----------------------------------------

def _kernel_case(lq, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    b, h, width, r, bs, mb = 3, 4, 256, 128, 8, 6
    pool = jnp.asarray(rng.normal(size=(1 + b * mb, bs, width)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, lq, width)) * 0.3, dtype)
    table = np.zeros((b, mb), np.int32)             # dead entries: scratch
    lengths = [13, 48, 1]
    for row, n in enumerate(lengths):
        used = -(-n // bs)
        table[row, :used] = 1 + row * mb + np.arange(used)
    q_pos = np.stack([np.arange(n - lq, n) for n in lengths])
    return q, pool, jnp.asarray(table), jnp.asarray(q_pos, jnp.int32), r


@pytest.mark.parametrize("lq", [1, 4])
def test_the_kernel_matches_the_composition_with_dead_table_entries(lq):
    q, pool, table, q_pos, r = _kernel_case(lq)
    want = fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                      route="composition")
    got = fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                     route="pallas")
    assert got.shape == (3, 4, lq, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the third row holds one position: with lq 4 its first queries see
    # nothing (q_pos < 0) and give 0, not NaN
    assert np.isfinite(np.asarray(got)).all()
    # garbage in the blocks no live entry names moves nothing
    dirty = pool.at[0].set(1e4)
    again = fa.latent_decode_attention(q, dirty, table, q_pos, r, 0.2,
                                       route="pallas")
    np.testing.assert_allclose(again, got, atol=0)
    # 16 or 4 rows of 8 positions an entry: a step's entries are one tile
    assert pd.latent_sub_blocks(6, 4 * lq, 8) == (6, 6)


def test_the_tile_width_follows_the_shapes():
    """``(sub, tile)``: a grid step takes the largest divisor of the table
    width up to 8 entries, and scores as many of them together as keep
    ``rows x tile x block_size`` float32 scores within 1 MiB."""
    assert pd._LATENT_SCORE_TILE == 256 * 1024
    # the cell (64 heads, one position, blocks of 128, 72 entries a row):
    # a step's eight entries are ONE tile of 1,024 positions
    assert pd.latent_sub_blocks(72, 64, 128) == (8, 8)
    # verify chunks of 2, 4 and 8 positions: the tile narrows once the
    # rows pass 256, down to an entry a tile (the old schedule)
    assert pd.latent_sub_blocks(72, 128, 128) == (8, 8)
    assert pd.latent_sub_blocks(72, 256, 128) == (8, 8)
    assert pd.latent_sub_blocks(72, 512, 128) == (8, 4)
    assert pd.latent_sub_blocks(72, 1024, 128) == (8, 2)
    assert pd.latent_sub_blocks(72, 2048, 128) == (8, 1)
    # an entry alone past the budget is still a tile of its own
    assert pd.latent_sub_blocks(72, 4096, 128) == (8, 1)
    # the tile divides the step: 6 entries at room for 4 go 3 and 3
    assert pd.latent_sub_blocks(6, 512, 128) == (6, 3)
    assert pd.latent_sub_blocks(6, 4, 8) == (6, 6)
    assert pd.latent_sub_blocks(7, 4, 8) == (7, 7)
    assert pd.latent_sub_blocks(7, 512, 128) == (7, 1)
    assert pd.latent_sub_blocks(11, 4, 8) == (1, 1)
    # smaller blocks, more of them a tile, never more than the step has
    assert pd.latent_sub_blocks(72, 512, 64) == (8, 8)
    assert pd.latent_sub_blocks(72, 2048, 16) == (8, 8)


def _offsets_case(lq, h, bs, dtype=jnp.float32, seed=0):
    """A table of two grid steps of eight entries, and one batch row for
    every place a row's last live entry can take in them (``last`` = 0 ...
    15, so ``last % sub`` = 0 ... 7 in the first step and in the second),
    then a row that sees nothing and a row that sees the whole table.
    Dead table entries name block 0.  Returns also the pool with 1e4 in
    every block that no live entry names."""
    rng = np.random.default_rng(seed)
    mb, width, r = 16, 256, 128
    lengths = [last * bs + 1 + (last * 5) % bs for last in range(mb)] \
        + [0, mb * bs]
    b = len(lengths)
    pool = jnp.asarray(rng.normal(size=(1 + b * mb, bs, width)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, lq, width)) * 0.3, dtype)
    table = np.zeros((b, mb), np.int32)
    named = np.zeros(1 + b * mb, bool)
    for row, n in enumerate(lengths):
        used = -(-n // bs)
        table[row, :used] = 1 + row * mb + np.arange(used)
        named[table[row, :used]] = True
    assert [(n - 1) // bs for n in lengths[:mb]] == list(range(mb))
    q_pos = np.stack([np.arange(n - lq, n) for n in lengths])
    dirty = jnp.where(jnp.asarray(named)[:, None, None], pool, 1e4)
    return (q, pool, dirty, jnp.asarray(table),
            jnp.asarray(q_pos, jnp.int32), r)


@pytest.fixture
def score_budget(monkeypatch):
    """Sets the tile's budget for one test: the rule is read as the kernel
    is traced, so the traced kernels are dropped before and after."""
    def set_to(elements):
        monkeypatch.setattr(pd, "_LATENT_SCORE_TILE", elements)
        pd._latent_call.clear_cache()
    yield set_to
    pd._latent_call.clear_cache()


@pytest.mark.parametrize("lq,h,bs,budget,tile", [
    (1, 4, 8, None, 8), (4, 4, 8, None, 8),       # a step's entries one tile
    (8, 4, 8, None, 8),
    (1, 64, 128, None, 8),                        # the cell's rows and blocks
    (8, 64, 128, None, 4),                        # a verify chunk: 4 + 4
    (4, 64, 128, 64 * 1024, 2),                   # narrower: four tiles of 2
    (8, 64, 128, 64 * 1024, 1)])                  # an entry a tile
def test_the_kernel_at_every_offset_of_a_rows_last_live_entry(
        lq, h, bs, budget, tile, score_budget):
    """Entries of a tile past the row's last live one are computed and add
    exactly nothing: whatever the blocks that no live entry names hold,
    the result is the clean pool's to the bit, and the composition's."""
    if budget is not None:
        score_budget(budget)
    q, pool, dirty, table, q_pos, r = _offsets_case(lq, h, bs)
    assert pd.latent_sub_blocks(table.shape[1], h * lq, bs) == (8, tile)
    want = fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                      route="composition")
    got = fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                     route="pallas")
    np.testing.assert_allclose(got, want, atol=2e-5)
    again = fa.latent_decode_attention(q, dirty, table, q_pos, r, 0.2,
                                       route="pallas")
    np.testing.assert_allclose(again, got, atol=0)
    # the row that sees nothing gives 0 beside the row that sees all
    assert np.abs(np.asarray(got[-2])).max() == 0.0
    assert np.abs(np.asarray(got[-1])).max() > 0.0
    # and so do the queries of a chunk that lie before position 0
    short = np.asarray(q_pos) < 0
    assert np.abs(np.asarray(got)[np.broadcast_to(
        short[:, None, :, None], got.shape)]).max(initial=0.0) == 0.0


@pytest.mark.parametrize("lq,offsets", [(1, False), (1, True), (8, True)])
def test_the_kernel_in_bfloat16_stays_near_the_float32_composition(lq,
                                                                   offsets):
    """The probabilities of a whole tile are rounded to the pool's type
    before their product with the latents, as an entry's were."""
    if offsets:
        q, _, pool, table, q_pos, r = _offsets_case(lq, 4, 8, jnp.bfloat16,
                                                    seed=3)
    else:
        q, pool, table, q_pos, r = _kernel_case(lq, jnp.bfloat16, seed=2)
    want = fa.latent_decode_attention(
        q.astype(jnp.float32), pool.astype(jnp.float32), table, q_pos, r,
        0.2, route="composition")
    got = fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                     route="pallas")
    assert got.dtype == jnp.bfloat16
    assert np.abs(np.asarray(got, np.float32) - want).max() < 0.05


# -- 4. refusals ----------------------------------------------------------------

def test_every_refusal_names_its_reason(monkeypatch):
    assert pd.latent_mosaic_refusal(64, 640, 512, 128) is None
    assert pd.latent_mosaic_refusal(64 * 8, 640, 512, 128) is None
    assert "576 values a position is not whole 128-lane tiles" \
        in pd.latent_mosaic_refusal(64, 576, 512, 128)
    assert "latent of 96 values" in pd.latent_mosaic_refusal(64, 128, 96, 128)
    assert "block of 12 positions" in pd.latent_mosaic_refusal(64, 640, 512,
                                                               12)
    assert "4 query rows" in pd.latent_mosaic_refusal(4, 640, 512, 128)
    q, pool, table, q_pos, r = _kernel_case(1)
    # a forced kernel on a TPU raises Mosaic's reason (4 heads x 1 row)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    with pytest.raises(InvalidArgumentError, match="4 query rows"):
        fa.latent_decode_attention(q, pool, table, q_pos, r, 0.2,
                                   route="pallas")
    monkeypatch.setattr(fa, "_backend_memo", None)
    # a cache by slot has no kernel
    with pytest.raises(InvalidArgumentError, match="by slot has no fused"):
        fa.latent_decode_attention(q, pool[:3], None, q_pos, r, 0.2,
                                   route="pallas")
    with pytest.raises(InvalidArgumentError, match="at most 8"):
        pd.latent_decode_attention_kernel(
            jnp.zeros((3, 4, 9, 256)), pool, table, jnp.zeros((3, 9),
                                                              jnp.int32),
            r, 0.2, interpret=True)
    with pytest.raises(InvalidArgumentError, match="first 128 lanes"):
        pd.latent_decode_attention_kernel(q[..., :128], pool, table, q_pos,
                                          r, 0.2, interpret=True)
    with pytest.raises(InvalidArgumentError, match="table must be"):
        pd.latent_decode_attention_kernel(q, pool, table[:2], q_pos, r, 0.2,
                                          interpret=True)
    with pytest.raises(InvalidArgumentError, match="q_pos must be"):
        pd.latent_decode_attention_kernel(q, pool, table, q_pos[:, :0], r,
                                          0.2, interpret=True)
    layer = LatentAttention(64, 4, 32, 128, 16, 8, 16)
    with pytest.raises(InvalidArgumentError, match="no per-head scale"):
        layer.gen_decode_cache(1, 16, "int8")
    with pytest.raises(InvalidArgumentError, match="'dense' or 'paged'"):
        layer.gen_decode_cache(1, 16, "float32", layout="recurrent")
    with pytest.raises(InvalidArgumentError, match="num_blocks >= 2"):
        layer.gen_decode_cache(1, 16, "float32", layout="paged",
                               num_blocks=1)
    with pytest.raises(InvalidArgumentError, match="qk_rope_head_dim 7"):
        LatentAttention(64, 4, 32, 128, 16, 7, 16)


def test_the_prompt_path_is_gated_from_shapes(monkeypatch):
    """Off the TPU the composition; on one, the flash kernel from 1,024
    positions of whole 512-tiles on, at head sizes it can hold padded."""
    assert not fa.causal_flash_supported((1, 64, 8192, 192), 128,
                                         jnp.bfloat16)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    assert fa.causal_flash_supported((1, 64, 8192, 192), 128, jnp.bfloat16)
    assert fa.causal_flash_supported((1, 64, 1024, 192), 128, jnp.float32)
    assert not fa.causal_flash_supported((1, 64, 512, 192), 128,
                                         jnp.bfloat16)
    assert not fa.causal_flash_supported((1, 64, 1280, 192), 128,
                                         jnp.bfloat16)
    assert not fa.causal_flash_supported((1, 64, 2048, 320), 128,
                                         jnp.bfloat16)
    assert not fa.causal_flash_supported((1, 64, 2048, 192), 128, jnp.int8)
    monkeypatch.setattr(fa, "_backend_memo", None)
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(1, 2, 12, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, 12, 16)), jnp.float32)
    got = fa.causal_attention(q, k, v, 0.3)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * 0.3
    s = np.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkv->bhqv", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=2e-5)
