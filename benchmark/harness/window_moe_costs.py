"""Bytes and operations of the window / global sparse-expert decoder,
computed from shapes: what the shares and rooflines that PERF.md section 7
defines for ``smallthinker21b-batch-closed`` divide by
(``calibrate_window_moe.py --by-hand``).  The decode step's figures are
memory-side (at 16 rows it passes its bytes long before its operations); the
prefill's are operations."""
from __future__ import annotations

from . import window_moe_weights as ww


def _item(cfg: dict) -> int:
    return 2 if cfg["engine"]["cache_dtype"] == "bfloat16" else 4


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in ONE layer."""
    return 2 * _item(cfg) * cfg["num_key_value_heads"] * cfg["head_dim"]


def layer_kinds(cfg: dict) -> tuple:
    """``(global layers, window layers)`` of the layers that are run."""
    windowed, _ = ww.layouts(cfg)
    return len(windowed) - sum(windowed), sum(windowed)


def ring_blocks(cfg: dict) -> int:
    """Blocks a slot pins in one window entry."""
    e = cfg["engine"]
    return min(cfg["sliding_window_size"] // e["block_size"] + 1,
               -(-cfg["max_len"] // e["block_size"]))


def pool_bytes(cfg: dict) -> dict:
    """What the cache holds, by kind: the global entries' ``num_blocks``
    blocks each, the window entries' ``slots x ring + 1`` each."""
    e = cfg["engine"]
    n_global, n_window = layer_kinds(cfg)
    block = e["block_size"] * kv_bytes_per_position(cfg)
    return {"paged": n_global * e["num_blocks"] * block,
            "window": n_window * (e["slots"] * ring_blocks(cfg) + 1) * block}


def weight_leaf_bytes(cfg: dict, weight_bytes: int, floor: int) -> int:
    """Bytes of the model's parameters in arrays of ``floor`` bytes or
    more (the storage census counts no smaller one)."""
    leaves = [ww.top_shapes(cfg)] + [ww.layer_shapes(cfg)] \
        * cfg["num_layers"]
    total = 0
    for shapes in leaves:
        for shape in shapes.values():
            n = weight_bytes
            for s in shape:
                n *= s
            total += n if n >= floor else 0
    return total


def in_window_positions(cfg: dict, contexts) -> float:
    """Positions ONE windowed call reads for rows at ``contexts``: the band,
    in whole blocks, as the walk copies them."""
    bs = cfg["engine"]["block_size"]
    w = cfg["sliding_window_size"]
    total = 0
    for top in contexts:
        first = max(top - w + 1, 0) // bs
        total += (top // bs - first + 1) * bs
    return float(total)


def windowed_calls_min_bytes(cfg: dict, window_live_blocks: float) -> float:
    """The least the windowed calls of ONE decode step move:
    ``window_live_blocks`` (``tick.decode``'s meta: the ring entries one
    call walks, over the live rows) blocks of K and V, once a window
    layer."""
    return window_live_blocks * cfg["engine"]["block_size"] \
        * kv_bytes_per_position(cfg) * layer_kinds(cfg)[1]


def global_calls_min_bytes(cfg: dict, live_blocks: float) -> float:
    """The same for the global layers' calls (``live_blocks``)."""
    return live_blocks * cfg["engine"]["block_size"] \
        * kv_bytes_per_position(cfg) * layer_kinds(cfg)[0]


def expert_bytes(cfg: dict, weight_bytes: int) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"] * weight_bytes


def experts_touched(cfg: dict, rows: float) -> float:
    """Experts of a layer that ``rows`` rows touch, each choosing evenly."""
    e, k = cfg["moe_num_primary_experts"], \
        cfg["moe_num_active_primary_experts"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_experts_min_bytes(cfg: dict, rows: float, weight_bytes: int) -> float:
    """The least the experts of ONE decode step read: the touched experts'
    matrices, every layer."""
    return cfg["num_layers"] * experts_touched(cfg, rows) \
        * expert_bytes(cfg, weight_bytes)


def attention_weight_count(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])


def decode_step_min_bytes(cfg: dict, rows: float, live_blocks: float,
                          window_live_blocks: float,
                          weight_bytes: int) -> float:
    """The least one batched decode step must move: the touched experts,
    the attention projections and the router of every layer, the head, and
    the K/V the live rows reach: all of it on a global layer, the band on
    a window layer.  The embedding's rows and the norms are left out."""
    return moe_experts_min_bytes(cfg, rows, weight_bytes) \
        + cfg["num_layers"] * (attention_weight_count(cfg)
                               + cfg["hidden_size"]
                               * cfg["moe_num_primary_experts"]) \
        * weight_bytes \
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes \
        + global_calls_min_bytes(cfg, live_blocks) \
        + windowed_calls_min_bytes(cfg, window_live_blocks)


def prefill_attention_flops(cfg: dict, length: int, banded: bool) -> float:
    """Operations of ONE layer's prompt attention over ``length``
    positions: two products of ``head_dim`` a (query, visible key) pair a
    query head, 2 operations a multiply-add.  A causal layer sees ``L (L +
    1) / 2`` pairs; a banded one the same up to the window, ``window`` a
    query beyond."""
    w = cfg["sliding_window_size"]
    n = min(length, w) if banded else length
    pairs = n * (n + 1) / 2 + (length - n) * w
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
