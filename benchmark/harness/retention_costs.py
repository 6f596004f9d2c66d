"""Bytes of the power-retention decoder's decode step, computed from shapes:
what the roofline shares divide by.  The step is memory-bound: at 16 rows
its operations over the compute peak are a twentieth of the time its bytes
take."""
from __future__ import annotations

PHI_TILE = 16       # the program holds phi in 16 x 16 tiles (configuration,
                    # ``assumed.phi_layout``)


def phi_size(cfg: dict) -> int:
    n = cfg["head_dim"] // PHI_TILE
    return PHI_TILE * PHI_TILE * n * (n + 1) // 2


def state_bytes_per_slot_layer(cfg: dict) -> int:
    """``S`` [Hkv, dv, D] and ``z`` [Hkv, D] of one slot of one layer,
    float32."""
    return 4 * cfg["num_key_value_heads"] * phi_size(cfg) \
        * (cfg["head_dim"] + 1)


def state_bytes_per_slot(cfg: dict) -> int:
    return cfg["num_layers"] * state_bytes_per_slot_layer(cfg)


def retention_step_min_bytes(cfg: dict, live_rows: float) -> float:
    """The least the retention steps of ONE decode step move: every live
    row's state read once and written once, in every layer.  ``phi(q)``,
    ``phi(k)``, ``v`` and the outputs (a hundredth of that) are left out."""
    return 2.0 * live_rows * state_bytes_per_slot(cfg)


def layer_weights(cfg: dict) -> int:
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * d * (2 * nq + 2 * nkv) + 3 * h * f + h * nkv


def decode_step_min_bytes(cfg: dict, live_rows: float,
                          weight_bytes: int) -> float:
    """The least one batched decode step must move: every layer's weights
    and the head once (the embedding rows gathered, the norm vectors and
    the activations are left out) and every live row's state in and out."""
    weights = cfg["num_layers"] * layer_weights(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    return weights * weight_bytes + retention_step_min_bytes(cfg, live_rows)
