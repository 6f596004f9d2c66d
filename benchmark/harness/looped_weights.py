"""Seeded weights for the looped decoder (``ouro``), made by the benchmark
one layer at a time as ``mamba_weights.py`` makes jamba's: the float32
reference holds a layer at a time, and makes it again in every pass (the
passes share the layers' weights).  Every value is exactly representable in
bfloat16, so the program and the reference start from identical numbers.

Matrices are normal with deviation ``assumed.initializer_std`` (0.02 as in
the other configurations).  The norms' scales are 1 + 0.02 normal and the
gate's bias 0.02 normal, NOT the release's initial 1 and 0: a layer has four
norms, and at exactly 1 a program that swapped two of them or dropped one's
scale would pass.  Initialisers, listed under the configuration's
``assumed``.  The head is a matrix of its own (untied).
"""
from __future__ import annotations

import functools

from .weights import MATRIX_STD, VECTOR_STD, seed32


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names
    (numbers only: the tuple of its items keys the reference's compile)."""
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "passes": cfg["total_ut_steps"],
            "threshold": float(cfg["early_exit_threshold"]),
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"])}


def model_kwargs(cfg: dict) -> dict:
    """``LoopedLM``'s arguments from the configuration."""
    s = sizes(cfg)
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"], num_heads=s["num_heads"],
                num_kv_heads=s["num_kv_heads"], head_dim=s["head_dim"],
                intermediate_size=cfg["intermediate_size"],
                total_ut_steps=s["passes"],
                early_exit_threshold=s["threshold"],
                rope_theta=s["rope_theta"], norm_epsilon=s["norm_eps"],
                dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict) -> dict:
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"in_norm": (h,), "wq": (h, nq * d), "wk": (h, nkv * d),
            "wv": (h, nkv * d), "wo": (nq * d, h), "attn_out_norm": (h,),
            "post_norm": (h,), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h), "mlp_out_norm": (h,)}


def top_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"embed": (cfg["vocab_size"], h), "final_norm": (h,),
            "exit_w": (h, 1), "exit_b": (1,), "head": (h, cfg["vocab_size"])}


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str, std: float):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            x = (1.0 if name.endswith("_norm") else 0.0) \
                + (VECTOR_STD if len(shape) == 1 else std) \
                * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out
    return jax.jit(draw)


def _make(cfg: dict, shapes: dict, seed: int, stream: int,
          dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    return _drawer(tuple(shapes.items()), dtype,
                   float(cfg["assumed"].get("initializer_std",
                                            MATRIX_STD)))(key)


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding, final norm, the exit gate and the head."""
    return _make(cfg, top_shapes(cfg), seed, 0, dtype)


def make_layer(cfg: dict, seed: int, i: int, dtype: str = "float32") -> dict:
    return _make(cfg, layer_shapes(cfg), seed, 1 + i, dtype)


def make_weights(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """The whole tree at once: small configurations and tests only."""
    out = make_top(cfg, seed, dtype)
    out["layers"] = [make_layer(cfg, seed, i, dtype)
                     for i in range(cfg["num_layers"])]
    return out


_TOP_NAMES = {"embed": "word_embeddings.weight",
              "final_norm": "final_norm.weight",
              "exit_w": "early_exit_gate.weight",
              "exit_b": "early_exit_gate.bias", "head": "lm_head.weight"}
_LAYER_NAMES = {
    "in_norm": "input_norm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.out_proj.weight",
    "attn_out_norm": "attn_out_norm.weight", "post_norm": "post_norm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight", "mlp_out_norm": "mlp_out_norm.weight"}


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
        put(make_layer(cfg, seed, i, dtype), _LAYER_NAMES, "layers.%d." % i)
    if params:
        raise RuntimeError("the benchmark has no weights for %s"
                           % sorted(params))
