"""Seeded weights for the window / global sparse-expert decoder
(``smallthinker``), made by the benchmark one layer at a time as
``blockdiff_weights.py`` makes sdar's: the cut is 11.1 GB in bfloat16, so
neither the program's load nor the float32 reference may hold it whole
beside something else.  Every value is exactly representable in bfloat16, so
both start from identical numbers.

The deviations are the configuration's (``assumed``), by the matrix's role,
because 0.02 everywhere makes two of this model's mechanisms invisible in
the served logits: ``qk_initializer_std`` for ``wq`` and ``wk`` (the scores'
spread: attention has to prefer some keys, or a window of 4,096 and a
context of 12,000 give nearly the same mean of values), ``vo_initializer_std``
for ``wv`` and ``wo`` (what attention adds to the stream beside what the
experts add), ``router_initializer_std`` (the router reads the stream as it
stands, NOT a normalised one, so its logits' spread is the stream's size
times this), ``embed_initializer_std`` (the stream's size at the first
layer's router), ``initializer_std`` for everything else.  The norms' scales are
1 + 0.02 normal.
"""
from __future__ import annotations

import functools

from .weights import MATRIX_STD, VECTOR_STD, seed32

_ROLE = {"embed": "embed_initializer_std",
         "wq": "qk_initializer_std", "wk": "qk_initializer_std",
         "wv": "vo_initializer_std", "wo": "vo_initializer_std",
         "router": "router_initializer_std"}


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names
    (numbers only: the tuple of its items keys the reference's compile)."""
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "top_k": cfg["moe_num_active_primary_experts"],
            "window": cfg["sliding_window_size"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"])}


def layouts(cfg: dict) -> tuple:
    """``(sliding_window_layout, rope_layout)`` of the layers that are run:
    the first ``num_layers`` of the published lists."""
    n = cfg["num_layers"]
    return (tuple(cfg["sliding_window_layout"][:n]),
            tuple(cfg["rope_layout"][:n]))


def model_kwargs(cfg: dict) -> dict:
    """``WindowMoELM``'s arguments from the configuration."""
    s = sizes(cfg)
    windowed, turned = layouts(cfg)
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"], num_heads=s["num_heads"],
                num_kv_heads=s["num_kv_heads"], head_dim=s["head_dim"],
                expert_size=cfg["moe_ffn_hidden_size"],
                num_experts=cfg["moe_num_primary_experts"],
                top_k=s["top_k"], window=s["window"],
                sliding_window_layout=windowed, rope_layout=turned,
                rope_theta=s["rope_theta"], norm_epsilon=s["norm_eps"],
                dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    return {"in_norm": (h,), "wq": (h, nq * d), "wk": (h, nkv * d),
            "wv": (h, nkv * d), "wo": (nq * d, h), "post_norm": (h,),
            "router": (h, e), "w_gate": (e, h, f), "w_up": (e, h, f),
            "w_down": (e, f, h)}


def top_shapes(cfg: dict) -> dict:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": (v, h), "final_norm": (h,), "head": (h, v)}


def deviations(cfg: dict, shapes: dict) -> tuple:
    """The deviation each leaf of ``shapes`` is drawn with."""
    assumed = cfg["assumed"]
    base = float(assumed.get("initializer_std", MATRIX_STD))
    return tuple(VECTOR_STD if len(shape) == 1
                 else float(assumed.get(_ROLE.get(name, ""), base))
                 for name, shape in shapes.items())


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, stds: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), std, k in zip(
                shapes, stds, jax.random.split(key, len(shapes))):
            x = (1.0 if len(shape) == 1 else 0.0) \
                + std * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out
    return jax.jit(draw)


def _make(cfg: dict, shapes: dict, seed: int, stream: int,
          dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    return _drawer(tuple(shapes.items()), deviations(cfg, shapes),
                   dtype)(key)


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding, final norm and the untied head."""
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
              "final_norm": "final_norm.weight", "head": "lm_head.weight"}
_LAYER_NAMES = {
    "in_norm": "input_norm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.out_proj.weight", "post_norm": "post_norm.weight",
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
