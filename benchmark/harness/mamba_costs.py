"""Bytes of the hybrid Mamba / attention decoder's steps, computed from
shapes: what the roofline shares divide by.  All three are memory-side
figures: the decode step at 64 rows passes its bytes long before its
operations, and the prefill scan is bound by neither (one exponential a
state entry a position: it reads low, and PERF.md says so)."""
from __future__ import annotations

from . import mamba_weights as mw


def state_bytes_per_slot_layer(cfg: dict) -> tuple:
    """``(ssm, conv)`` bytes of one slot of one Mamba layer: ``[N, C]``
    float32 and ``[K - 1, C]`` in the weights' type."""
    s = mw.sizes(cfg)
    conv_item = 2 if cfg["weights_dtype"] == "bfloat16" else 4
    return (4 * s["d_state"] * s["d_inner"],
            conv_item * (s["d_conv"] - 1) * s["d_inner"])


def mamba_layers(cfg: dict) -> int:
    return sum(not mw.is_attention(cfg, i) for i in range(cfg["num_layers"]))


def ssm_bytes_per_slot(cfg: dict) -> int:
    """The float32 scan state of one slot, every Mamba layer."""
    return mamba_layers(cfg) * state_bytes_per_slot_layer(cfg)[0]


def scan_step_min_bytes(cfg: dict, live_rows: float) -> float:
    """The least the scan steps of ONE decode step move: every live row's
    scan state and convolution state read once and written once, in every
    Mamba layer.  ``dt``, ``c``, ``B``, ``C`` and ``y`` (a twentieth of
    that) are left out."""
    return 2.0 * live_rows * mamba_layers(cfg) \
        * sum(state_bytes_per_slot_layer(cfg))


def weight_count(cfg: dict) -> int:
    """Parameters a decode step reads: every layer's matrices and the
    embedding once as the head (vectors left out)."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for i in range(cfg["num_layers"]):
        total += sum(a * b[0] if b else 0 for a, *b in
                     mw.layer_shapes(cfg, i).values())
    return total


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position over the attention layers."""
    item = 2 if cfg["weights_dtype"] == "bfloat16" else 4
    return 2 * item * cfg["num_key_value_heads"] * mw.head_dim(cfg) \
        * (cfg["num_layers"] - mamba_layers(cfg))


def decode_step_min_bytes(cfg: dict, live_rows: float, live_blocks: float,
                          weight_bytes: int) -> float:
    """The least one batched decode step must move: the weights once, every
    live row's states in and out, and the K/V blocks the live rows'
    positions reach (``live_blocks`` table entries of
    ``engine.block_size`` positions)."""
    return weight_count(cfg) * weight_bytes \
        + scan_step_min_bytes(cfg, live_rows) \
        + live_blocks * cfg["engine"]["block_size"] \
        * kv_bytes_per_position(cfg)


def prefill_scan_min_bytes(cfg: dict, positions: float) -> float:
    """The least the scans of ONE prefill of ``positions`` (the bucket)
    move: ``dt`` (float32) and ``c`` read once, ``y`` (float32) written
    once, ``B`` and ``C`` read once, a position a Mamba layer; the state
    once in and once out."""
    s = mw.sizes(cfg)
    c_item = 2 if cfg["weights_dtype"] == "bfloat16" else 4
    a_position = s["d_inner"] * (4 + c_item + 4) + 2 * 4 * s["d_state"]
    return mamba_layers(cfg) * (positions * a_position
                                + 2 * state_bytes_per_slot_layer(cfg)[0])
