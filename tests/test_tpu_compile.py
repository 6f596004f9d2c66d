"""What the TPU's compiler makes of the decode step, with no TPU.

The compiler for the v5e is installed here and compiles for a chip that
is described, not attached (nothing runs).  These tests compile the
serving cells' decode step at their real cache geometry and read the
optimized program: a layout the CPU backend never chooses is the thing
to guard.  ``chip_smoke.py`` makes the same check on the chip.

Keep every such compile in THIS file: one process may hold the TPU's
library, the worker that is given this file loads it inside the fixture,
and a second file could land on a worker where it cannot.
"""
import importlib
import re

import numpy as np
import pytest

import chip_smoke
import paddle_tpu as pt
from paddle_tpu.inference import GenerationPool
from paddle_tpu.models import TransformerLM


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any reason is a skip
        pytest.skip("no v5e topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent cache
    # and can never be read back from it: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


LAYERS = 2


@pytest.fixture(scope="module")
def model():
    # gpt-1p3b's width and heads, two layers, a small vocabulary:
    # neither depth nor the head changes a write
    pt.seed(0)
    model = TransformerLM(vocab_size=512, hidden_size=2048,
                          num_layers=LAYERS, num_heads=16,
                          intermediate_size=8192, max_position=1024,
                          causal=True, dropout=0.0)
    model.eval()
    return model


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_pool_decode_on_the_v5e_moves_no_pool(one_chip, model, monkeypatch,
                                              cache_dtype):
    # the cells' cache: 512 blocks x 16 heads x 32 positions x 128, 16
    # slots, on the fused kernel's route.  The write must update the
    # donated pool where it lies, in the row-major layout the kernel is
    # pinned to: with ``H`` left as a window dimension of the scatter
    # this program held four copies of a 134 MB pool a layer, a third of
    # the step (PERF.md, PR 26 and 27)
    import jax

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pool = GenerationPool(model, max_len=1024, slots=16, buckets=[128],
                          cache_layout="paged", block_size=32,
                          num_blocks=512, cache_dtype=cache_dtype)
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    # the route's gate asks for the backend, which is the CPU here:
    # answer for the chip this compile is for
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    text = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    # a float pool's K and V rows go by ONE kernel a layer beside the
    # attention's (``ops.pallas_decode._kv_write_call``: both pools
    # aliased in and out, so all that has a pool's shape is the step's
    # parameters and the elements of the kernels' results); an int8
    # pool keeps its four scatters a layer (K, V and their scales)
    quant = cache_dtype == "int8"
    assert text.count('custom_call_target="tpu_custom_call"') \
        == LAYERS * (1 if quant else 2)
    pool_shape = pool._cache[0].k.shape
    assert pool_shape == (512, 16, 32, 128)
    assert chip_smoke.pool_shaped_moves(text, pool_shape) == []
    # and the writes are there, on the pool as the step was given it
    made = [op for _, op in chip_smoke.pool_shaped_ops(text, pool_shape)]
    assert made.count("scatter") == (2 * LAYERS if quant else 0)
    assert made.count("get-tuple-element") == (0 if quant else 2 * LAYERS)
    # (copy-start/copy-done: the scheduler's prefetch of an int8 pool
    # into another memory space for the kernel, the parent's too; since
    # the kernel takes the pool whole it comes in slices, joined by a
    # ``ConcatBitcast`` custom call; no layout changes hands there)
    assert set(made) <= ({"parameter", "scatter", "fusion", "bitcast",
                          "copy-start", "copy-done", "custom-call"}
                         if quant else
                         {"parameter", "get-tuple-element",
                          "bitcast"}), sorted(set(made))
    for name, op in chip_smoke.pool_shaped_ops(text, pool_shape):
        if op == "custom-call":
            line = text[text.index("%%%s = " % name):].split("\n", 1)[0]
            assert 'custom_call_target="ConcatBitcast"' in line, line
    assert made.count("fusion") == made.count("scatter")
    # the sampler's sort over [slots, vocabulary] stays inside the
    # conditional's drawing branch: the TPU's compiler keeps the
    # ``cond`` a conditional, and a greedy step's entry computation (the
    # module's last) holds no sort (docs/DESIGN.md 5q)
    entry = text[text.index("\nENTRY "):]
    assert " sort(" in text and " sort(" not in entry
    assert entry.count(" conditional(") == 1


@pytest.mark.parametrize("lq", [1, 5])
@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_paged_kernel_at_the_gpt_cells_geometry_on_the_v5e(one_chip, lq,
                                                           cache_dtype):
    """The kernel alone at ``gpt-1p3b``'s cache: every head of an entry
    in one copy ([16, 32, 128] of K and of V: two float32 entries a tile
    of the walk, five int8 ones with their scales a step's 160 positions
    in a row of 256 lanes), one row and a verify chunk's five, the pools
    left in HBM.  One custom call each."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((512, 16, 32, 128), jnp.dtype(cache_dtype))
    args = [shape((16, 16, lq, 128), jnp.float32), pool, pool,
            shape((16, 32), jnp.int32), shape((16, lq), jnp.int32)]
    if cache_dtype == "int8":
        args += [shape((512, 16, 32), jnp.float32)] * 2
    assert pallas_decode.head_chunk(16, 32, 128, pool.dtype.itemsize,
                                    cache_dtype == "int8") == 16
    assert pallas_decode.paged_tile_entries(
        16, lq, 32, 128, pool.dtype.itemsize, 32) == (
            5 if cache_dtype == "int8" else 2)
    text = jax.jit(
        lambda q, k, v, t, p, *scales:
        pallas_decode.paged_decode_attention_kernel(
            q, k, v, t, p, 128 ** -0.5, *scales)).lower(
        *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize(
    "cell,rows,heads,kv_heads,lq,block,entries,blocks,tile", [
        ("zaya", 64, 8, 2, 1, 128, 24, 1537, 8),
        ("zaya-verify", 64, 8, 2, 8, 128, 24, 1537, 8),
        ("jamba", 64, 20, 1, 1, 128, 18, 1153, 8),
        ("bias", 8, 8, 8, 4, 128, 8, 65, 2),
        ("bias-32", 8, 8, 8, 4, 32, 32, 257, 8)])
def test_the_paged_walk_compiles_at_the_bfloat16_cells_on_the_v5e(
        one_chip, cell, rows, heads, kv_heads, lq, block, entries, blocks,
        tile):
    """The walk over a row's live entries under Mosaic at ``zaya1-8b``'s
    call (2 K/V heads under 8 query heads, blocks of 128, bfloat16: eight
    entries a tile, copied by hand into [2, 1024, 128] buffers) at one
    position and at a verify chunk's eight, at ``jamba2-3b``'s (one K/V
    head under 20 query heads: 20 rows, a table of 18 that no tile of 8
    divides), and with an additive bias laid out by the walk's steps, at
    blocks of 128 and of 32 (a quarter of a lane tile an entry).
    ``sdar-30b-a3b``'s call is the grouped test's, below."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    has_bias = cell.startswith("bias")
    group = heads // kv_heads
    assert pallas_decode.paged_mosaic_refusal(128, block) is None
    assert pallas_decode.paged_tile_entries(
        pallas_decode.head_chunk(kv_heads, block, 128, 2), group * lq,
        block, 128, 2, entries) == tile
    pool = shape((blocks, kv_heads, block, 128), bf)
    args = [shape((rows, heads, lq, 128), bf), pool, pool,
            shape((rows, entries), jnp.int32), shape((rows, lq), jnp.int32)]
    if has_bias:
        args.append(shape((rows, 1, lq, entries * block), jnp.float32))
    text = jax.jit(
        lambda q, k, v, t, p, *bias:
        pallas_decode.paged_decode_attention_kernel(
            q, k, v, t, p, 128 ** -0.5, bias=bias[0] if bias else None)
    ).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_grouped_heads_kernel_and_grouped_matmul_on_the_v5e(one_chip,
                                                            monkeypatch):
    """The block-diffusion cell's two kernels at its real shapes: the
    paged kernel with the 8 query heads of a K/V head as one block of 32
    rows over a bfloat16 pool [641, 4, 128, 128], and the expert layer's
    grouped matmuls at a 2,048-row prefill (one ``ragged-dot`` custom
    call each) beside its every-expert route at a 128-row step (none).
    Interpret mode cannot refuse a tile; this compiler can."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import moe
    from paddle_tpu.ops import pallas_decode

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    text = jax.jit(
        lambda q, k, v, t, p: pallas_decode.paged_decode_attention_kernel(
            q, k, v, t, p, 128 ** -0.5)).lower(
        shape((32, 32, 4, 128), bf), shape((641, 4, 128, 128), bf),
        shape((641, 4, 128, 128), bf), shape((32, 20), jnp.int32),
        shape((32, 4), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    experts = [shape((128, 2048, 768), bf), shape((128, 2048, 768), bf),
               shape((128, 768, 2048), bf)]
    layer = jax.jit(lambda x, s, *w: moe.sparse_experts(x, s, *w, top_k=8))
    for rows, grouped in ((2048, 3), (128, 0)):
        text = layer.lower(shape((rows, 2048), bf),
                           shape((rows, 128), jnp.float32),
                           *experts).compile().as_text()
        assert len(set(re.findall(r"ragged-dot-none[\w.]* = ",
                                  text))) == grouped, rows


def test_block_step_on_the_v5e_runs_two_blocks_a_slot_through_the_kernels(
        one_chip, monkeypatch):
    """``sdar-30b-a3b``'s block step at the cell's cache (641 blocks of 128
    positions, 4 K/V heads of 128, bfloat16, 32 slots), its six layers at
    their widths, 8 of the 128 experts held and a small vocabulary
    (neither changes a write, a route or a row count; the whole model is
    8.7 GB): the chunk is two blocks of 4, so a layer's attention is ONE
    ``_paged_call`` on 8 query rows a slot and its K and V rows go by ONE
    in-place write kernel, 256 rows; every expert runs on every row (no
    grouped matmul, no loop); nothing of a pool's size is copied; and the
    head's product has ``slots x B`` rows, not ``slots x 2B``."""
    import jax

    from paddle_tpu.inference import BlockDiffusionPool
    from paddle_tpu.models import BlockDiffusionMoELM
    from paddle_tpu.nn.functional import moe

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    layers, slots, bl = 6, 32, 4
    pt.seed(0)
    sdar = BlockDiffusionMoELM(
        vocab_size=512, hidden_size=2048, num_layers=layers, num_heads=32,
        num_kv_heads=4, head_dim=128, expert_size=768, num_experts=128,
        top_k=8, block_length=bl, mask_token_id=511, denoise_steps=2,
        dtype="bfloat16", held_experts=(0, 8))
    sdar.eval()
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    pool = BlockDiffusionPool(sdar, 2560, slots=slots, buckets=[512],
                              cache_layout="paged", block_size=128,
                              num_blocks=641, cache_dtype="bfloat16")
    assert pool._rows_a_slot == 2 * bl
    assert pool._entries_meta["kv_write"] == "kernel"
    # from shapes alone, at the share held here and at the cell's whole
    assert pool._expert_route == "every" == moe.expert_route(
        slots * 2 * bl, 128, 128, 8, 2048, 768, 2)
    params, bufs = pool._session._state_vals()
    args = (params, bufs, pool._cache, np.zeros((slots, 2 * bl), np.int32),
            np.zeros((slots, 2 * bl + 4), np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    text = jax.jit(pool._block_step, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    calls = [line for line in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    attn = [c for c in calls if "_paged_call" in c.split(" = ")[0]]
    write = [c for c in calls if "_kv_write_call" in c.split(" = ")[0]]
    assert len(attn) == len(write) == layers and len(calls) == 2 * layers
    # ``q_pos`` a row of the chunk; the group's 8 heads x 8 rows folded
    assert all("s32[%d,%d]{1,0}, bf16[%d,4,64,128]"
               % (slots, 2 * bl, slots) in c for c in attn)
    assert all("s32[%d]{0}, s32[%d]{0}, bf16[%d,4,128]"
               % ((slots * 2 * bl,) * 3) in c for c in write)
    assert "ragged-dot" not in text and " while(" not in text
    pool_shape = pool._cache[0].k.shape
    assert pool_shape == (641, 4, 128, 128)
    assert chip_smoke.pool_shaped_moves(text, pool_shape) == []
    assert {op for _, op in chip_smoke.pool_shaped_ops(text, pool_shape)} \
        <= {"parameter", "get-tuple-element", "bitcast"}
    head = [line for line in text.split("\n")
            if "/lm_head/dot_general" in line and " convolution(" in line]
    assert head and all(" = bf16[%d,%d,512]" % (slots, bl) in line
                        for line in head)


def test_the_touched_route_on_the_v5e_copies_no_expert(one_chip):
    """ax-k1's expert layer at a decode step's shapes (32 rows, 12 of 192
    experts of 7168 x 2048 held, 8 a token): a loop on the device over the
    touched experts, whose three slices of the stacked weights are
    operands of their products (beside it, in a conditional, every expert
    on every row for the step whose rows chose them all).  A slice taken outside the loop's body
    materialises 88 MB a matrix and costs more than a skipped expert
    saves: no instruction outside a fusion makes an expert-sized matrix,
    and the program's temporaries stay under one."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import moe

    rows, held, experts, k, width, size = 32, 12, 192, 8, 7168, 2048
    assert moe.expert_route(rows, held, experts, k, width, size, 2) \
        == "touched"
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
            for dims, dtype in (
                ((rows, width), bf), ((rows, experts), jnp.float32),
                ((held, width, size), bf), ((held, width, size), bf),
                ((held, size, width), bf))]
    compiled = jax.jit(lambda x, s, *w: moe.sparse_experts(
        x, s, *w, top_k=k, first_expert=24, scoring="sigmoid", n_group=8,
        topk_group=4, routed_scale=2.5)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 1 and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < width * size * 2
    matrix = re.compile(r" = bf16\[(1,)?(%d,%d|%d,%d)\]\S* (?!parameter|"
                        r"get-tuple-element|fusion)"
                        % (width, size, size, width))
    outside = [line.strip()[:160]
               for block in re.split(r"\n(?=%|ENTRY )", text)
               if not block.startswith("%fused_computation")
               for line in block.split("\n") if matrix.search(line)]
    assert outside == []
    # the products that take the stacked weights whole: a turn's three,
    # each with the turn's expert (a scalar operand) sliced inside it, and
    # the three of every expert on every row, the branch taken where the
    # rows chose all twelve
    products = [b.split("\n", 1) for b in re.split(r"\n(?=%|ENTRY )", text)
                if b.startswith("%fused_computation")
                and "bf16[%d," % held in b.split("\n", 1)[0]
                and " convolution(" in b]
    turn = [body for head, body in products if "s32[]" in head]
    assert len(turn) == 3 and all(" fusion(" in body for body in turn)
    assert len(products) == 6 and text.count(" conditional(") == 1


def test_retention_step_on_the_v5e_updates_the_state_where_it_lies(
        one_chip, monkeypatch):
    """The power-retention cell's decode step at its real state geometry
    (16 slots x 8 K/V heads x [128, 9216] float32, 38 MB a slot a layer):
    one Pallas kernel a layer, the state aliased in and out, and no
    instruction anywhere in the program whose result has the state's shape
    but the parameters and what the kernels return: no copy, no select.
    Two layers at the published widths, a small vocabulary."""
    import jax

    from paddle_tpu.models import PowerRetentionLM
    from paddle_tpu.ops import power_retention as pr

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pt.seed(0)
    model = PowerRetentionLM(vocab_size=512, hidden_size=5120,
                             num_layers=LAYERS, num_heads=40,
                             num_kv_heads=8, head_dim=128,
                             intermediate_size=17408)
    model.eval()
    pool = GenerationPool(model, max_len=4864, slots=16, buckets=[1024],
                          cache_layout="recurrent")
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    state_shape = pool._cache[0].state.shape
    assert state_shape == (16, 8, 128, 9216)
    assert pr.step_kernel_refusal(state_shape) is None
    assert pr.step_tile(9216, 128) == 9216
    compiled = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == LAYERS
    made = {op for _, op in chip_smoke.pool_shaped_ops(text, state_shape)}
    assert made <= {"parameter", "get-tuple-element", "custom-call",
                    "bitcast"}, sorted(made)
    # every byte of S and z goes out in the buffer it came in
    state_bytes = LAYERS * 16 * pr.state_bytes(8, 128, 128)
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes


def test_hybrid_decode_step_on_the_v5e_updates_both_states_in_place(
        one_chip, monkeypatch):
    """``jamba2-3b``'s step at its widths and cache geometry, three layers
    (Mamba, attention, Mamba): 64 slots, ``ssm`` [64, 16, 5120] float32 and
    ``conv`` [64, 3, 5120] bfloat16 beside a paged bfloat16 pool of one K/V
    head under 20 query heads (20 rows a block of the kernel: the block is
    the whole array's rows, so Mosaic takes it unpadded).  One custom call
    a layer, no ``copy`` of a scan state or of the K/V pool, and every
    state byte goes out in the buffer it came in."""
    import jax

    from paddle_tpu.models import HybridMambaLM
    from paddle_tpu.ops import selective_scan as ss

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pt.seed(0)
    model = HybridMambaLM(vocab_size=512, hidden_size=2560, num_layers=3,
                          num_heads=20, num_kv_heads=1, head_dim=128,
                          intermediate_size=8192, attn_layer_period=3,
                          attn_layer_offset=1)
    model.eval()
    pool = GenerationPool(model, max_len=2304, slots=64, buckets=[1024],
                          cache_layout="paged", block_size=128,
                          cache_dtype="bfloat16")
    assert pool.cache_layout == "paged+recurrent"
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    ssm_shape = pool._cache[0].ssm.shape
    kv_shape = pool._cache[1].k.shape
    assert ssm_shape == (64, 16, 5120)
    assert kv_shape == (64 * 18 + 1, 1, 128, 128)
    assert ss.step_kernel_refusal(ssm_shape) is None
    compiled = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile()
    text = compiled.as_text()
    # a scan step a Mamba layer, the attention layer's paged kernel and
    # its K/V write
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    made = {op for _, op in chip_smoke.pool_shaped_ops(text, ssm_shape)}
    assert made <= {"parameter", "get-tuple-element", "custom-call",
                    "bitcast"}, sorted(made)
    assert chip_smoke.pool_shaped_moves(text, kv_shape) == []
    assert chip_smoke.pool_shaped_moves(text, pool._cache[0].conv.shape) \
        == []
    state_bytes = 2 * 64 * 16 * 5120 * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes


def test_cca_step_on_the_v5e_keeps_both_entries_where_they_lie(
        one_chip, monkeypatch):
    """``zaya1-8b``'s step at its widths and cache geometry, two layers, a
    small vocabulary: 64 slots of 24 blocks of 128 positions, 2 K/V heads of
    128 under 8 query heads (bfloat16), and beside each K/V entry a state
    of 1,280 + 1,280 + 128 values a slot.  One paged kernel a layer, the
    K/V pools written where they lie by one more, 16 experts on 64 rows through the
    every-expert route (no grouped matmul).  Then the 1,024 bucket's prefill: the
    flash kernel over the prompt's own keys, a layer."""
    import jax

    from paddle_tpu.models import CCAMoELM

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pt.seed(0)
    model = CCAMoELM(vocab_size=512, hidden_size=2048, num_layers=LAYERS,
                     num_heads=8, num_kv_heads=2, head_dim=128,
                     conv_taps=(2, 2), moe_intermediate_size=2048,
                     num_experts=16, top_k=1, router_hidden_size=256,
                     rope_theta=5e6, partial_rotary_factor=0.5)
    model.eval()
    pool = GenerationPool(model, max_len=3072, slots=64, buckets=[1024],
                          cache_layout="paged", block_size=128,
                          num_blocks=1537, cache_dtype="bfloat16")
    assert pool.cache_layout == "paged+recurrent"
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    kv_shape = pool._cache[0].k.shape
    assert kv_shape == (1537, 2, 128, 128)
    assert [f.shape for f in pool._cache[1][:3]] == [(64, 1280), (64, 1280),
                                                     (64, 128)]
    text = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    # the paged kernel and the K/V write's (64 rows of 2 heads, K and V
    # in one call), a layer
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * LAYERS
    assert chip_smoke.pool_shaped_moves(text, kv_shape) == []
    # (copy-start / copy-done and a ``ConcatBitcast``: at two layers the
    # scheduler has room to prefetch one pool into another memory space
    # for the kernel, the parent's program too; no layout changes hands)
    made = {op for _, op in chip_smoke.pool_shaped_ops(text, kv_shape)}
    assert made <= {"parameter", "get-tuple-element", "bitcast",
                    "copy-start", "copy-done", "custom-call"}, sorted(made)
    assert "ragged-dot" not in text
    sess = pool._session
    ids = jax.ShapeDtypeStruct((1, 1024), np.int32, sharding=one_chip)
    true_len = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    row_samp = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip),
        sess.sampling_state(1))
    text = jax.jit(sess._prefill).lower(
        shapes[0], shapes[1], ids, true_len, row_samp).compile().as_text()
    # the flash kernel, once a layer, and no paged kernel: the prompt
    # attends its own keys
    assert text.count('custom_call_target="tpu_custom_call"') == LAYERS


def test_latent_step_on_the_v5e_moves_no_pool_and_its_kernels_compile(
        one_chip, monkeypatch):
    """The latent-attention cell's cache geometry (2,305 blocks of 128
    positions x 640 values of bfloat16, 32 slots of 72 blocks, 64 heads on
    one latent of 512 + 64), two layers (one dense, one of experts with a
    share held) at narrow feed-forwards: one Pallas kernel a layer, the
    pool written by ONE scatter a layer where it lies and copied nowhere
    (a separate [.., 128, 64] pool of rotary keys was: the chip prefers
    positions minor-most for it, the kernel cannot read that, and two
    pool-sized copies a layer a step went there and back).  Then the
    kernel alone at a verify chunk, and the prompt's causal attention at
    head sizes 192 / 128 through the flash kernel padded to 256."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import LatentMoELM
    from paddle_tpu.ops import pallas_decode

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pt.seed(0)
    model = LatentMoELM(
        vocab_size=512, hidden_size=1024, num_layers=LAYERS, num_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=2048,
        moe_intermediate_size=256, num_experts=192, top_k=8, n_group=8,
        topk_group=4, routed_scaling_factor=2.5, held_experts=(0, 12),
        rope_scaling={"factor": 32, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096})
    model.eval()
    pool = GenerationPool(model, max_len=9216, slots=32, buckets=[2048],
                          cache_layout="paged", block_size=128,
                          num_blocks=2305, cache_dtype="bfloat16")
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    pool_shape = pool._cache[0].latent.shape
    assert pool_shape == (2305, 128, 640)
    assert pallas_decode.latent_mosaic_refusal(64, 640, 512, 128) is None
    assert pallas_decode.latent_sub_blocks(72, 64, 128) == (8, 8)
    text = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == LAYERS
    assert chip_smoke.pool_shaped_moves(text, pool_shape) == []
    made = [op for _, op in chip_smoke.pool_shaped_ops(text, pool_shape)]
    assert made.count("scatter") == LAYERS
    assert set(made) <= {"parameter", "scatter", "fusion", "bitcast"}, \
        sorted(set(made))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    text = jax.jit(
        lambda q, c, t, p: pallas_decode.latent_decode_attention_kernel(
            q, c, t, p, 512, 0.13)).lower(
        shape((32, 64, 4, 640), bf), shape(pool_shape, bf),
        shape((32, 72), jnp.int32), shape((32, 4), jnp.int32)) \
        .compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert fa.causal_flash_supported((1, 64, 2048, 192), 128, bf)
    text = jax.jit(lambda q, k, v: fa.causal_attention(q, k, v, 0.13)).lower(
        shape((1, 64, 2048, 192), bf), shape((1, 64, 2048, 192), bf),
        shape((1, 64, 2048, 128), bf)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("lq,tile", [(1, 8), (8, 4)])
def test_the_latent_kernel_compiles_at_the_tile_its_rows_give(one_chip, lq,
                                                              tile):
    """The latent kernel alone for the v5e at the cell's geometry (32 rows
    of 64 heads, one position: a grid step's eight entries scored as ONE
    tile of 1,024 positions) and at the speculative pool's (a verify chunk
    of eight, 512 query rows: four entries a tile): one Mosaic kernel
    each."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode

    assert pallas_decode.latent_mosaic_refusal(64 * lq, 640, 512, 128) is None
    assert pallas_decode.latent_sub_blocks(72, 64 * lq, 128) == (8, tile)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    text = jax.jit(
        lambda q, c, t, p: pallas_decode.latent_decode_attention_kernel(
            q, c, t, p, 512, 0.13)).lower(
        shape((32, 64, lq, 640), bf), shape((2305, 128, 640), bf),
        shape((32, 72), jnp.int32), shape((32, lq), jnp.int32)) \
        .compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_looped_step_on_the_v5e_is_one_loop_that_moves_no_plane(
        one_chip, monkeypatch):
    """``ouro-2p6b``'s decode step and prefill at the cell's cache (81 blocks
    of 64 positions, FOUR planes of 16 heads of 128 an entry, bfloat16, 16
    slots), two of its 48 layers, a small vocabulary: the passes are ONE
    ``while`` with the layers' two paged kernels in its body (not eight in
    a row), a layer's K and V pools are written by one kernel where they
    lie, on the loop's own carry (a plane's rows at a traced head offset),
    and nothing of a pool's size, nor of a plane's, is copied, sliced or
    re-laid, in the step or in the prefill's row cache, whose longer chunk
    keeps the scatters."""
    import jax

    from paddle_tpu.models import LoopedLM

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pt.seed(0)
    looped = LoopedLM(vocab_size=512, hidden_size=2048, num_layers=LAYERS,
                      num_heads=16, num_kv_heads=16, head_dim=128,
                      intermediate_size=5632, total_ut_steps=4,
                      dtype="bfloat16")
    looped.eval()
    pool = GenerationPool(looped, max_len=320, slots=16, buckets=[128],
                          cache_layout="paged", block_size=64,
                          num_blocks=81, cache_dtype="bfloat16")
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    text = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    pool_shape = pool._cache[0].k.shape
    assert pool_shape == (81, 4 * 16, 64, 128)
    plane_shape = (81, 16, 64, 128)
    assert text.count(" while(") == 1
    # a layer's paged kernel and its K/V write, in the loop's body
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * LAYERS
    for shape in (pool_shape, plane_shape):
        assert chip_smoke.pool_shaped_moves(text, shape) == []
    made = [op for _, op in chip_smoke.pool_shaped_ops(text, pool_shape)]
    assert set(made) <= {"parameter", "bitcast",
                         "get-tuple-element"}, sorted(set(made))
    assert [op for _, op in chip_smoke.pool_shaped_ops(text, plane_shape)] \
        == []
    # the prefill: a row cache of 1 + 5 blocks, the same loop
    sess = pool._session
    pargs = (params, bufs, np.zeros((1, 128), np.int32), np.int32(100),
             sess.sampling_state(1))
    pshapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=one_chip), pargs)
    text = jax.jit(sess._prefill).lower(*pshapes).compile().as_text()
    assert text.count(" while(") == 1
    for shape in ((6, 64, 64, 128), (6, 16, 64, 128)):
        assert chip_smoke.pool_shaped_moves(text, shape) == []


@pytest.mark.parametrize("lq", [1, 5])
def test_the_planed_kernel_at_the_looped_cells_geometry_on_the_v5e(one_chip,
                                                                    lq):
    """The kernel at ``ouro-2p6b``'s cache: the pools hold four planes of
    16 heads an entry, the call attends the plane at a TRACED head offset
    (one more scalar-prefetch operand), an entry's copy is [16, 64, 128]
    bfloat16 of K and of V: gpt-1p3b's 524,288 B."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((81, 64, 64, 128), jnp.bfloat16)
    args = [shape((16, 16, lq, 128), jnp.bfloat16), pool, pool,
            shape((16, 5), jnp.int32), shape((16, lq), jnp.int32),
            shape((), jnp.int32)]
    assert pallas_decode.head_chunk(16, 64, 128, 2) == 16
    text = jax.jit(
        lambda q, k, v, t, p, base:
        pallas_decode.paged_decode_attention_kernel(
            q, k, v, t, p, 128 ** -0.5, head_base=base,
            plane_heads=16)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize(
    "cell,pool_shape,rows,heads,lq,dtype,planed", [
        ("gpt", (512, 16, 32, 128), 16, 16, 1, "float32", False),
        ("gpt-verify", (512, 16, 32, 128), 16, 16, 8, "float32", False),
        ("ouro", (81, 64, 64, 128), 16, 16, 1, "bfloat16", True),
        ("zaya", (1537, 2, 128, 128), 64, 2, 1, "bfloat16", False),
        ("jamba", (1153, 1, 128, 128), 64, 1, 1, "bfloat16", False),
        ("sdar", (641, 4, 128, 128), 32, 4, 4, "bfloat16", False)])
def test_the_kv_write_kernel_compiles_at_the_cells_on_the_v5e(
        one_chip, cell, pool_shape, rows, heads, lq, dtype, planed):
    """The K/V write alone under Mosaic at every cell that writes a float
    paged pool: ``gpt-1p3b``'s (float32, blocks of 32: a group of 8 rows)
    at one position and at a verify chunk's eight (more groups than the
    budget holds: four slots a grid step), ``ouro-2p6b``'s (bfloat16, a
    group of 16 rows, the plane at a TRACED head offset of a pool of four),
    ``zaya1-8b``'s (64 rows of 2 heads), ``jamba2-3b``'s (one head) and
    ``sdar-30b-a3b``'s (a block's four positions a slot, which share a
    group).  One custom call, both donated pools aliased whole into the
    results, nothing of a pool's shape made."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_decode

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    dt = jnp.dtype(dtype)
    assert pallas_decode.kv_write_mosaic_refusal(
        pool_shape[3], pool_shape[2], dt.itemsize) is None
    assert pallas_decode.write_group(pool_shape[2], dt.itemsize) \
        == {"float32": 8, "bfloat16": 16}[dtype]
    new = shape((rows, heads, lq, 128), dt)
    at = shape((rows, lq), jnp.int32)
    compiled = jax.jit(
        lambda k, v, kn, vn, phys, off, base:
        pallas_decode.paged_kv_write_kernel(
            k, v, kn, vn, phys, off,
            head_base=base if planed else None),
        donate_argnums=(0, 1)).lower(
        shape(pool_shape, dt), shape(pool_shape, dt), new, new, at, at,
        shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert chip_smoke.pool_shaped_moves(text, pool_shape) == []
    made = {op for _, op in chip_smoke.pool_shaped_ops(text, pool_shape)}
    assert made <= {"parameter", "get-tuple-element", "bitcast"}, \
        sorted(made)
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * int(np.prod(pool_shape)) * dt.itemsize


def test_the_kv_write_kernel_names_what_mosaic_refuses():
    """A pool whose head is half a lane tile, and a bfloat16 pool of
    blocks of 8 (half a packed tile of rows): refused by name, so the
    auto route keeps the scatter there and a forced route raises."""
    from paddle_tpu.ops import pallas_decode

    assert "lane" in pallas_decode.kv_write_mosaic_refusal(64, 32, 4)
    assert "rows" in pallas_decode.kv_write_mosaic_refusal(128, 8, 2)
    assert pallas_decode.kv_write_mosaic_refusal(128, 8, 4) is None
    assert pallas_decode.write_group(8, 2) == 8     # the interpreter's


def _window_model(layers: int):
    from paddle_tpu.models import WindowMoELM

    pt.seed(0)
    # smallthinker-21b-a3b's attention at its published widths (28 heads on
    # 4 of 128, window 4,096, the two layouts), two small experts and a
    # small vocabulary: neither changes a cache entry or a kernel
    layout = ([0, 1, 1, 1] * 3)[:layers]
    model = WindowMoELM(vocab_size=512, hidden_size=2560, num_layers=layers,
                        num_heads=28, num_kv_heads=4, head_dim=128,
                        expert_size=768, num_experts=2, top_k=1,
                        window=4096, sliding_window_layout=layout,
                        rope_layout=layout, dtype="bfloat16")
    model.eval()
    return model


def _custom_calls(text):
    return [line for line in text.split("\n")
            if 'custom_call_target="tpu_custom_call"' in line]


def test_window_step_on_the_v5e_walks_two_kinds_of_entry(one_chip,
                                                         monkeypatch):
    """``smallthinker-21b-a3b``'s decode step at the cell's cache (16 slots,
    blocks of 128, 1,281 blocks under the three global entries' table, a
    ring of 33 blocks a slot in each of the nine window entries' own pools
    of 529, bfloat16): 3 ``_paged_call`` without a window and 9 with, 12
    K/V write kernels, and nothing of either pool's size copied or
    re-laid."""
    import jax

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pool = GenerationPool(_window_model(12), max_len=16384, slots=16,
                          buckets=[2048], cache_layout="paged",
                          block_size=128, num_blocks=1281,
                          cache_dtype="bfloat16")
    assert pool.cache_layout == "paged+window"
    shapes_of = {tuple(c.k.shape) for c in pool._cache}
    assert shapes_of == {(1281, 4, 128, 128), (529, 4, 128, 128)}
    assert pool.cache_stats()["bytes_per_slot"] == {
        "paged": 3 * 16384 * 2048, "window": 9 * 33 * 128 * 2048}
    n = pool.slots
    params, bufs = pool._session._state_vals()
    samp = (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, np.uint32))
    args = (params, bufs, pool._cache, np.zeros(n, np.int32),
            np.ones(n, bool), samp, np.zeros(n, np.uint32),
            np.zeros(n, np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    text = jax.jit(pool._pool_decode, donate_argnums=(2,)) \
        .lower(*shapes).compile().as_text()
    calls = _custom_calls(text)
    windowed = [c for c in calls if "paged_attn/window/" in c]
    plain = [c for c in calls if "paged_attn/jit(_paged_call)" in c]
    writes = [c for c in calls if "jit(_kv_write_call)" in c]
    assert (len(plain), len(windowed), len(writes)) == (3, 9, 12)
    assert len(calls) == 24
    for shape in shapes_of:
        assert chip_smoke.pool_shaped_moves(text, shape) == []
        made = {op for _, op in chip_smoke.pool_shaped_ops(text, shape)}
        assert made <= {"parameter", "get-tuple-element",
                        "bitcast"}, sorted(made)


def test_a_prompt_of_12288_on_the_v5e_holds_no_square_of_scores(
        one_chip, monkeypatch):
    """The prefill at the cell's longest bucket, one period of the layers
    (a global layer and three window layers): every layer's prompt
    attention is a splash kernel (causal or banded: the band's block lists
    are 9 wide where the causal ones are 24), and no array of the program
    has two dimensions of 12,288: 28 heads of float32 scores would be
    16.9 GB."""
    import jax

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pool = GenerationPool(_window_model(4), max_len=16384, slots=16,
                          buckets=[12288], cache_layout="paged",
                          block_size=128, num_blocks=1281,
                          cache_dtype="bfloat16")
    sess = pool._session
    params, bufs = sess._state_vals()
    pargs = (params, bufs, np.zeros((1, 12288), np.int32), np.int32(11936),
             sess.sampling_state(1))
    pshapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=one_chip), pargs)
    monkeypatch.setattr(fa, "_backend_memo", "tpu")
    text = jax.jit(sess._prefill).lower(*pshapes).compile().as_text()
    splash = [c for c in _custom_calls(text) if "splash" in c]
    assert len(splash) == 4
    assert sum("s8[1,24,9]" in c for c in splash) == 3      # the band
    assert sum("s8[1,24,24]" in c for c in splash) == 1     # causal
    assert re.findall(r"\[[0-9,]*12288,12288[0-9,]*\]", text) == []
