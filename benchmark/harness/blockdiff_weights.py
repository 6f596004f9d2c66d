"""Seeded weights for the block-diffusion sparse-expert decoder, made by the
benchmark one layer at a time: the released size is 8.7 GB in bfloat16 and
17 GB in float32, so neither the program's load nor the float32 reference
may ever hold it whole beside something else.

``make_top`` and ``make_layer`` draw from the seed alone, so the program's
model is loaded with one call's output (``load_into``, bfloat16) and the
reference is given the same call's output again (float32), layer by layer.
Every value is exactly representable in bfloat16, so both start from
identical numbers.
"""
from __future__ import annotations

import functools

from .weights import MATRIX_STD, VECTOR_STD, seed32


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names."""
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "top_k": cfg["num_experts_per_tok"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "block_length": cfg["block_length"],
            "mask_token_id": cfg["mask_token_id"],
            "denoise_steps": cfg["denoise_steps"]}


def model_kwargs(cfg: dict) -> dict:
    """``BlockDiffusionMoELM``'s arguments from the configuration."""
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                expert_size=cfg["moe_intermediate_size"],
                num_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                block_length=cfg["block_length"],
                mask_token_id=cfg["mask_token_id"],
                denoise_steps=cfg["denoise_steps"],
                rope_theta=float(cfg["rope_theta"]),
                norm_epsilon=float(cfg["rms_norm_eps"]),
                dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"in_norm": (h,), "wq": (h, nq * d), "wk": (h, nkv * d),
            "wv": (h, nkv * d), "wo": (nq * d, h), "q_norm": (d,),
            "k_norm": (d,), "post_norm": (h,), "router": (h, e),
            "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h)}


def top_shapes(cfg: dict) -> dict:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": (v, h), "final_norm": (h,), "head": (h, v)}


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            norm = len(shape) == 1
            x = (1.0 if norm else 0.0) + (VECTOR_STD if norm
                                          else MATRIX_STD) \
                * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out
    return jax.jit(draw)


def _make(shapes: dict, seed: int, stream: int, dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    return _drawer(tuple(shapes.items()), dtype)(key)


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding, final norm and the untied head."""
    return _make(top_shapes(cfg), seed, 0, dtype)


def make_layer(cfg: dict, seed: int, i: int, dtype: str = "float32") -> dict:
    return _make(layer_shapes(cfg), seed, 1 + i, dtype)


def make_weights(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """The whole tree at once: small configurations and tests only."""
    out = make_top(cfg, seed, dtype)
    out["layers"] = [make_layer(cfg, seed, i, dtype)
                     for i in range(cfg["num_layers"])]
    return out


_TOP_NAMES = {"embed": "word_embeddings.weight",
              "final_norm": "final_norm.weight", "head": "lm_head.weight"}
_LAYER_NAMES = {
    "in_norm": "input_norm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.out_proj.weight", "q_norm": "self_attn.q_norm.weight",
    "k_norm": "self_attn.k_norm.weight", "post_norm": "post_norm.weight",
    "router": "moe.router", "w_gate": "moe.w_gate", "w_up": "moe.w_up",
    "w_down": "moe.w_down"}


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
        put(make_layer(cfg, seed, i, dtype), _LAYER_NAMES,
            "layers.%d." % i)
    if params:
        raise RuntimeError("the benchmark has no weights for %s"
                           % sorted(params))
