"""Seeded weights for the latent-attention expert decoder (``axk1``), made by
the benchmark one layer at a time as ``mamba_weights.py`` makes jamba's: the
float32 reference holds a layer at a time.  Every value of a leaf the
program keeps in bfloat16 is exactly representable in bfloat16, so the
program and the reference start from identical numbers.

Matrices are normal with deviation ``assumed.initializer_std`` (0.02 as in
the other configurations) and norm vectors 1 + 0.02 normal.  The ROUTER has
an initialiser of its own, ``assumed.router_initializer_std``: its scores
are ``sigmoid(m W_r)`` over a normed ``m``, so their spread is ``sqrt(hidden)
x std``; at the published width 0.02 gives logits of deviation 1.69 (scores
0.08 to 0.92) and stays; a toy width needs a larger one or every score sits
at 0.5 and rounding chooses the experts.

Only the experts held here are drawn (``n_routed_experts`` of them from
``first_held_expert``; the router keeps ``router_experts`` outputs), and only
the ``vocab_size`` rows of the embedding and the head held here.
"""
from __future__ import annotations

import functools

from .weights import MATRIX_STD, VECTOR_STD, seed32


def is_dense(cfg: dict, i: int) -> bool:
    """Layer ``i`` has the dense feed-forward (``first_k_dense_replace``;
    ``moe_layer_freq`` is 1: every later layer routes)."""
    return i < cfg["first_k_dense_replace"]


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names
    (numbers only: the tuple of its items keys the reference's compile)."""
    rs = cfg["rope_scaling"]
    return {"num_heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"],
            "norm_eps": float(cfg["rms_norm_eps"]),
            "rope_theta": float(cfg["rope_theta"]),
            "rope_factor": float(rs["factor"]),
            "rope_original": int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "mscale": float(rs["mscale"]),
            "mscale_all_dim": float(rs["mscale_all_dim"]),
            "router_experts": cfg["router_experts"],
            "first_held": cfg["first_held_expert"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
            "routed_scale": float(cfg["routed_scaling_factor"])}


def model_kwargs(cfg: dict) -> dict:
    """``LatentMoELM``'s arguments from the configuration."""
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=cfg["rope_scaling"],
        norm_epsilon=float(cfg["rms_norm_eps"]),
        dtype=cfg["weights_dtype"],
        held_experts=(cfg["first_held_expert"], cfg["n_routed_experts"]))


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i``."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    out = {"in_norm": (h,), "w_dq": (h, qr), "q_norm": (qr,),
           "w_uq": (qr, n * (dn + dr)), "w_dkv": (h, r + dr),
           "kv_norm": (r,), "w_ukv": (r, n * (dn + dv)),
           "w_o": (n * dv, h), "post_norm": (h,)}
    if is_dense(cfg, i):
        f = cfg["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        fs = cfg["n_shared_experts"] * f
        out.update(router=(h, cfg["router_experts"]),
                   e_gate=(held, h, f), e_up=(held, h, f),
                   e_down=(held, f, h), s_gate=(h, fs), s_up=(h, fs),
                   s_down=(fs, h))
    return out


def top_shapes(cfg: dict) -> dict:
    return {"embed": (cfg["vocab_size"], cfg["hidden_size"]),
            "final_norm": (cfg["hidden_size"],),
            "head": (cfg["hidden_size"], cfg["vocab_size"])}


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str, std: float, router_std: float):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            dev = VECTOR_STD if len(shape) == 1 else \
                router_std if name == "router" else std
            x = (1.0 if name.endswith("_norm") else 0.0) \
                + dev * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out
    return jax.jit(draw)


def _make(cfg: dict, shapes: dict, seed: int, stream: int,
          dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    std = float(cfg["assumed"].get("initializer_std", MATRIX_STD))
    return _drawer(tuple(shapes.items()), dtype, std,
                   float(cfg["assumed"].get("router_initializer_std",
                                            std)))(key)


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding, final norm and the head (untied) over the rows held."""
    return _make(cfg, top_shapes(cfg), seed, 0, dtype)


def make_layer(cfg: dict, seed: int, i: int, dtype: str = "float32") -> dict:
    return _make(cfg, layer_shapes(cfg, i), seed, 1 + i, dtype)


def make_weights(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """The whole tree at once: small configurations and tests only."""
    out = make_top(cfg, seed, dtype)
    out["layers"] = [make_layer(cfg, seed, i, dtype)
                     for i in range(cfg["num_layers"])]
    return out


_TOP_NAMES = {"embed": "word_embeddings.weight",
              "final_norm": "final_norm.weight", "head": "lm_head.weight"}
_ATTENTION_NAMES = {
    "in_norm": "input_norm.weight", "w_dq": "self_attn.q_down.weight",
    "q_norm": "self_attn.q_norm.weight", "w_uq": "self_attn.q_up.weight",
    "w_dkv": "self_attn.kv_down.weight",
    "kv_norm": "self_attn.kv_norm.weight",
    "w_ukv": "self_attn.kv_up.weight", "w_o": "self_attn.o_proj.weight",
    "post_norm": "post_norm.weight"}
_DENSE_NAMES = {"w_gate": "mlp.gate_proj.weight",
                "w_up": "mlp.up_proj.weight",
                "w_down": "mlp.down_proj.weight", **_ATTENTION_NAMES}
_MOE_NAMES = {"router": "moe.router", "e_gate": "moe.w_gate",
              "e_up": "moe.w_up", "e_down": "moe.w_down",
              "s_gate": "moe.shared.gate_proj.weight",
              "s_up": "moe.shared.up_proj.weight",
              "s_down": "moe.shared.down_proj.weight", **_ATTENTION_NAMES}


def load_into(model, cfg: dict, seed: int) -> None:
    """Put the benchmark's weights into the program's model, a layer at a
    time: each leaf replaces the program's own before the next is made."""
    params = dict(model.named_parameters())
    dtype = cfg["weights_dtype"]

    def put(made: dict, names: dict, prefix: str) -> None:
        for ours, theirs in names.items():
            params.pop(prefix + theirs)._replace_value(made.pop(ours))

    put(make_top(cfg, seed, dtype), _TOP_NAMES, "")
    for i in range(cfg["num_layers"]):
        put(make_layer(cfg, seed, i, dtype),
            _DENSE_NAMES if is_dense(cfg, i) else _MOE_NAMES,
            "layers.%d." % i)
    if params:
        raise RuntimeError("the benchmark has no weights for %s"
                           % sorted(params))
