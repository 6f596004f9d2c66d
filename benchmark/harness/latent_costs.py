"""Operations and bytes of the latent decode-attention kernel, computed from
shapes: what ``latent_attn_roofline.axk1`` divides by.  Every count is of
the LEAST work: what any implementation of the layer must read or compute,
not what this one does (the entry's padding to whole lanes, the blocks'
tails and the absorbed form's wider products are left out), so a share
cannot pass 100 %."""
from __future__ import annotations


def latent_bytes_per_position(cfg: dict, item: int = 2) -> int:
    """What one position keeps in ONE layer: the latent and the one rotary
    key (1,152 B in bfloat16 at the published sizes)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * item


def live_positions(cfg: dict, live_rows: float, live_blocks: float) -> float:
    """Positions the live rows hold, from the table entries they reach: a
    row's last block is half full on average."""
    return (live_blocks - 0.5 * live_rows) * cfg["engine"]["block_size"]


def latent_attn_min_bytes(cfg: dict, positions: float) -> float:
    """The least the decode attention of ONE step reads: every live
    position's latent and rotary key once a layer (the queries and the
    result, 64 x 576 values a row, are a thousandth of that)."""
    return cfg["num_layers"] * positions * latent_bytes_per_position(cfg)


def latent_attn_flops(cfg: dict, positions: float) -> float:
    """The least operations of the same: a score of width ``dn + dr`` and a
    value of width ``dv`` a head a position (the published head sizes; the
    absorbed form spends ``r + dr`` and ``r`` instead, to read 1,152 B a
    position in place of 40,960)."""
    per_head = 2.0 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                      + cfg["v_head_dim"])
    return cfg["num_layers"] * positions * cfg["num_attention_heads"] \
        * per_head
