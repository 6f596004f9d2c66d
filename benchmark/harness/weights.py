"""Seeded weights for a pre-norm transformer, made by the benchmark.

One jitted call makes every leaf on the device from the seed, in float32.
The program's model is loaded with them (``to_program_names``) and the
plain reference is given the same call's output again, so neither side
takes anything the other made.

Every value is exactly representable in bfloat16: a program that casts a
leaf to bf16 (the O2 train step) and a reference that keeps float32 then
start from identical numbers, whichever leaves the program chooses to cast.
"""
from __future__ import annotations

import numpy as np

MATRIX_STD = 0.02       # GPT-2 / BERT initializer range
VECTOR_STD = 0.02       # biases and norm offsets: not zero, so a dropped
                        # bias or norm parameter changes the output


def seed32(seed: int, stream: int = 0) -> int:
    """Any whole number (the driver's exceed 2**31) to a 31-bit seed, one
    per ``stream`` so weights, token ids and orders do not share draws."""
    return int(np.random.SeedSequence([int(seed), int(stream)])
               .generate_state(1)[0]) & 0x7FFFFFFF


def layer_shapes(cfg: dict) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"ln1_w": (h,), "ln1_b": (h,), "wq": (h, h), "bq": (h,),
            "wk": (h, h), "bk": (h,), "wv": (h, h), "bv": (h,),
            "wo": (h, h), "bo": (h,), "ln2_w": (h,), "ln2_b": (h,),
            "w1": (h, f), "b1": (f,), "w2": (f, h), "b2": (h,)}


def make_weights(cfg: dict, seed: int):
    """``{"wte", "wpe", "lnf_w", "lnf_b", "layers": [dict per layer]}`` of
    float32 arrays on the default device: one jitted call draws every kind
    of leaf for all layers at once."""
    import jax
    import jax.numpy as jnp

    n_layers = cfg["num_layers"]
    shapes = layer_shapes(cfg)

    def draw(key, shape, std, mean=0.0):
        x = mean + std * jax.random.normal(key, shape, jnp.float32)
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def build(key):
        keys = iter(jax.random.split(key, 4 + len(shapes)))
        top = {
            "wte": draw(next(keys), (cfg["vocab_size"], cfg["hidden_size"]),
                        MATRIX_STD),
            "wpe": draw(next(keys), (cfg["max_position"],
                                     cfg["hidden_size"]), MATRIX_STD),
            "lnf_w": draw(next(keys), (cfg["hidden_size"],), VECTOR_STD, 1.0),
            "lnf_b": draw(next(keys), (cfg["hidden_size"],), VECTOR_STD),
        }
        stacked = {}
        for name, shape in shapes.items():
            stacked[name] = draw(
                next(keys), (n_layers,) + shape,
                VECTOR_STD if len(shape) == 1 else MATRIX_STD,
                1.0 if name in ("ln1_w", "ln2_w") else 0.0)
        return top, stacked

    out, stacked = jax.jit(build)(jax.random.PRNGKey(seed32(seed, 1)))
    # one kind at a time is cut into its layers and its stack dropped, so
    # that no more than the largest stack is ever held twice
    layers = [{} for _ in range(n_layers)]
    for name in shapes:
        stack = stacked.pop(name)
        for i in range(n_layers):
            layers[i][name] = stack[i]
        del stack
    out["layers"] = layers
    return out


_PROGRAM_LAYER_NAMES = {
    "ln1_w": "norm1.weight", "ln1_b": "norm1.bias",
    "wq": "self_attn.q_proj.weight", "bq": "self_attn.q_proj.bias",
    "wk": "self_attn.k_proj.weight", "bk": "self_attn.k_proj.bias",
    "wv": "self_attn.v_proj.weight", "bv": "self_attn.v_proj.bias",
    "wo": "self_attn.out_proj.weight", "bo": "self_attn.out_proj.bias",
    "ln2_w": "norm2.weight", "ln2_b": "norm2.bias",
    "w1": "linear1.weight", "b1": "linear1.bias",
    "w2": "linear2.weight", "b2": "linear2.bias",
}


def to_program_names(weights: dict) -> dict:
    """The same arrays under ``TransformerLM.named_parameters()`` names."""
    out = {"word_embeddings.weight": weights["wte"],
           "position_embeddings.weight": weights["wpe"],
           "final_norm.weight": weights["lnf_w"],
           "final_norm.bias": weights["lnf_b"]}
    for i, layer in enumerate(weights["layers"]):
        for ours, theirs in _PROGRAM_LAYER_NAMES.items():
            out["encoder.layers.%d.%s" % (i, theirs)] = layer[ours]
    return out


def from_program(cfg: dict, lookup) -> dict:
    """A tree shaped like ``make_weights``'s, filled by ``lookup(program
    parameter name)``: how the program's state is lined up leaf for leaf
    with the reference's."""
    out = {"wte": lookup("word_embeddings.weight"),
           "wpe": lookup("position_embeddings.weight"),
           "lnf_w": lookup("final_norm.weight"),
           "lnf_b": lookup("final_norm.bias"), "layers": []}
    for i in range(cfg["num_layers"]):
        out["layers"].append({
            ours: lookup("encoder.layers.%d.%s" % (i, theirs))
            for ours, theirs in _PROGRAM_LAYER_NAMES.items()})
    return out


def leaf_names(tree: dict) -> list:
    """Names of ``jax.tree.leaves(tree)``, in that order."""
    import jax
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]
