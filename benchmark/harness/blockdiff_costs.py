"""Operations and bytes of the block-diffusion sparse-expert decoder's
step, computed from shapes: what the roofline shares divide by."""
from __future__ import annotations


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Experts a layer reads when ``rows`` tokens each choose ``top_k`` of
    ``num_experts`` uniformly (seeded random routers are near uniform):
    ``E * (1 - (1 - k/E)^rows)``.  At 128 rows: 127.97 of 128."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes(cfg: dict, weight_bytes: int) -> float:
    """One expert's three matrices."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * weight_bytes


def experts_min_bytes(cfg: dict, rows: float, weight_bytes: int) -> float:
    """The least the grouped matmuls of ONE step read: the touched
    experts' weights once in every layer, and each routed row's
    activations in and out of the three matmuls (small beside them)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = rows * cfg["num_experts_per_tok"]
    acts = pairs * (2 * h + 3 * f + h) * weight_bytes
    return cfg["num_layers"] * (
        expected_experts_touched(cfg, rows) * expert_bytes(cfg, weight_bytes)
        + acts)


def experts_flops(cfg: dict, rows: float) -> float:
    """Multiply-adds x 2 of one step's grouped matmuls."""
    return 2.0 * cfg["num_layers"] * rows * cfg["num_experts_per_tok"] \
        * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_weights(cfg: dict) -> float:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return h * d * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def kv_bytes_per_position(cfg: dict, kv_bytes: int) -> float:
    return 2.0 * cfg["num_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * kv_bytes


def step_min_bytes(cfg: dict, rows: float, live_positions: float,
                   weight_bytes: int, kv_bytes: int) -> float:
    """The least one block step must read from HBM: the touched experts',
    the attention's, the router's and the head's weights once, and the K
    and V of every live position of every layer.  Norm vectors, the
    embedding rows gathered and the activations are left out."""
    h = cfg["hidden_size"]
    per_layer = attention_weights(cfg) + h * cfg["num_experts"]
    return (cfg["num_layers"] * per_layer + h * cfg["vocab_size"]) \
        * weight_bytes \
        + cfg["num_layers"] * expected_experts_touched(cfg, rows) \
        * expert_bytes(cfg, weight_bytes) \
        + kv_bytes_per_position(cfg, kv_bytes) * live_positions
