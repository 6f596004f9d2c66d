"""Bytes of the looped decoder's decode step, computed from shapes: what
``decode_step_roofline.ouro`` and ``paged_calls_roofline.ouro`` (PERF.md
section 7) divide by.  Memory-side figures: at 16 rows the step passes its
bytes long before its operations."""
from __future__ import annotations

from . import looped_weights as lw


def layer_weight_count(cfg: dict) -> int:
    """Parameters of one layer's matrices (the norms' vectors left out)."""
    return sum(a * b[0] for a, *b in lw.layer_shapes(cfg).values() if b)


def plane_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in ONE plane of one layer."""
    item = 2 if cfg["engine"]["cache_dtype"] == "bfloat16" else 4
    return 2 * item * cfg["num_key_value_heads"] * cfg["head_dim"]


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position: every layer, every plane (one a pass)."""
    return cfg["num_layers"] * cfg["total_ut_steps"] \
        * plane_bytes_per_position(cfg)


def paged_calls_min_bytes(cfg: dict, live_blocks: float) -> float:
    """The least the paged attention calls of ONE decode step move (layers
    x passes of them): every live table entry's K and V of the call's own
    plane, once.  ``live_blocks`` counts a position's entry once (the
    ``tick.decode`` meta), whatever the planes."""
    return live_blocks * cfg["engine"]["block_size"] \
        * kv_bytes_per_position(cfg)


def decode_step_min_bytes(cfg: dict, live_blocks: float,
                          weight_bytes: int) -> float:
    """The least one batched decode step must move: the layers' weights
    ONCE A PASS (VMEM holds a layer at most, so nothing of a pass survives
    to the next), the head once, and the K/V the live rows' positions
    reach in every plane.  The embedding's rows, the norms and the gate
    are left out."""
    return cfg["total_ut_steps"] * cfg["num_layers"] \
        * layer_weight_count(cfg) * weight_bytes \
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes \
        + paged_calls_min_bytes(cfg, live_blocks)
