"""Seeded weights for the power-retention decoder (``brumby``), made by the
benchmark one layer at a time, as ``blockdiff_weights.py`` makes sdar's: the
released size is 8.4 GB in bfloat16 and twice that in float32, so neither
the program's load nor the float32 reference may hold it whole beside
something else.  Every value is exactly representable in bfloat16, so the
program (bfloat16) and the reference (float32) start from identical numbers.

Matrices are normal with deviation 0.02 and norm vectors 1 + 0.02 normal, as
in the other configurations.  THE GATE IS NOT: an untrained gate (bias 0)
sits at 0.5 and forgets in two tokens, and a comparison through the cache
would then say nothing of a state carried over thousands of positions.  Its
bias is drawn so that the memory length ``1 / (1 - g)`` of a layer's K/V
heads spans ``gate_memory`` = 64 to 4,096 positions, evenly in the
logarithm, the heads in a seeded order; its projection has deviation 0.002,
so that a token moves its gate a little and the projection is not dead
weight.  An initialiser, listed under the configuration's ``assumed``.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .weights import MATRIX_STD, VECTOR_STD, seed32

GATE_STD = 0.002


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names."""
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "eps": float(cfg["assumed"]["normaliser_eps"])}


def model_kwargs(cfg: dict) -> dict:
    """``PowerRetentionLM``'s arguments from the configuration."""
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                intermediate_size=cfg["intermediate_size"],
                rope_theta=float(cfg["rope_theta"]),
                norm_epsilon=float(cfg["rms_norm_eps"]),
                dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict) -> dict:
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"in_norm": (h,), "wq": (h, nq * d), "wk": (h, nkv * d),
            "wv": (h, nkv * d), "wo": (nq * d, h), "q_norm": (d,),
            "k_norm": (d,), "wg": (h, nkv), "post_norm": (h,),
            "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}


def top_shapes(cfg: dict) -> dict:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": (v, h), "final_norm": (h,), "head": (h, v)}


def gate_bias(cfg: dict, seed: int, layer: int) -> np.ndarray:
    """One bias a K/V head: ``log(memory - 1)`` for memory lengths evenly
    spaced in the logarithm over ``assumed.gate_memory``, the heads in an
    order drawn from the seed, rounded to bfloat16."""
    import jax.numpy as jnp

    lo, hi = cfg["assumed"]["gate_memory"]
    n = cfg["num_key_value_heads"]
    memory = [lo * (hi / lo) ** (j / max(1, n - 1)) for j in range(n)]
    order = np.random.default_rng([seed32(seed, 2), layer]).permutation(n)
    bias = np.asarray([math.log(memory[j] - 1.0) for j in order], np.float32)
    return np.asarray(jnp.asarray(bias).astype(jnp.bfloat16)
                      .astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            norm = len(shape) == 1
            std = GATE_STD if name == "wg" else (
                VECTOR_STD if norm else MATRIX_STD)
            x = (1.0 if norm else 0.0) \
                + std * jax.random.normal(k, shape, jnp.float32)
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
    import jax.numpy as jnp

    out = _make(layer_shapes(cfg), seed, 1 + i, dtype)
    out["bg"] = jnp.asarray(gate_bias(cfg, seed, i)).astype(dtype)
    return out


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
    "k_norm": "self_attn.k_norm.weight", "wg": "self_attn.gate_proj.weight",
    "bg": "self_attn.gate_proj.bias", "post_norm": "post_norm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight"}


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
