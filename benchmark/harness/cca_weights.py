"""Seeded weights for the CCA / routed-expert decoder (``zaya``), made by the
benchmark one layer at a time as ``mamba_weights.py`` makes jamba's: the
float32 reference holds a layer at a time.  Every value is exactly
representable in bfloat16, so the program and the reference start from
identical numbers.

Matrices are normal with deviation ``assumed.initializer_std`` (0.02 as in
the other configurations), norm vectors 1 + 0.02 normal, biases 0.02 normal.
WHAT THE MECHANISMS LIVE ON IS NOT, or no fault of them would reach a logit:

- the convolutions' taps have deviation ``1 / sqrt(fan_in)`` (``w0``: the
  ``k0`` taps of a channel; ``w1``: ``k1 x d`` a channel), so that the mixed
  part of q and k is as large as the part that skips the convolutions;
- ``temp`` (the keys' scale a K/V head) is uniform in 0.5-2 and ``gamma``
  (the weight of the router state of the layer before) uniform in 0.5-1.5:
  at 1 +- 0.02 a program that dropped either would pass;
- the router's three small matrices have deviation
  ``assumed.router_mlp_gain / sqrt(fan_in)``: at 0.02 the scores would have
  deviation 0.001, every probability 1/16 and rounding would choose the
  experts; at a gain of 2 the scores have deviation 2-4 and the chosen
  expert's probability spreads over 0.3-1.

Initialisers, listed under the configuration's ``assumed``.  The leaves have
the reference's layout (``w0`` ``[C, k0]``, ``w1`` ``[g, out, in, k1]``, q and
k, and the two value halves, as matrices of their own); ``load_into`` lays
them out as the program holds them.
"""
from __future__ import annotations

import functools
import math

from .weights import MATRIX_STD, VECTOR_STD, seed32

_ROUTER_MATRICES = ("r_w1", "r_w2", "r_w3")


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names
    (numbers only: the tuple of its items keys the reference's compile)."""
    if cfg["num_experts_per_tok"] != 1:
        raise ValueError("the reference routes one expert a token")
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "k0": cfg["cca_time0"], "k1": cfg["cca_time1"],
            "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "rope_theta": float(cfg["rope_parameters"]["hybrid"]
                                ["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"])}


def model_kwargs(cfg: dict) -> dict:
    """``CCAMoELM``'s arguments from the configuration."""
    s = sizes(cfg)
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"], num_heads=s["num_heads"],
                num_kv_heads=s["num_kv_heads"], head_dim=s["head_dim"],
                conv_taps=(s["k0"], s["k1"]),
                moe_intermediate_size=cfg["moe_intermediate_size"],
                num_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                router_hidden_size=cfg["router_hidden_size"],
                rope_theta=s["rope_theta"],
                partial_rotary_factor=cfg["partial_rotary_factor"],
                norm_epsilon=s["norm_eps"], dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` (the first layer's router has no
    ``r_gamma``: there is no layer before it)."""
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    r, c = cfg["router_hidden_size"], (n + nkv) * d
    out = {"in_norm": (h,), "w_q": (h, n * d), "w_k": (h, nkv * d),
           "w0": (c, cfg["cca_time0"]), "b0": (c,),
           "w1": (n + nkv, d, d, cfg["cca_time1"]), "b1": (n + nkv, d),
           "temp": (nkv,), "w_v1": (h, nkv * d // 2),
           "w_v2": (h, nkv * d // 2), "w_o": (n * d, h), "post_norm": (h,),
           "r_down": (h, r), "r_down_b": (r,), "r_gamma": (r,),
           "r_norm": (r,), "r_w1": (r, r), "r_b1": (r,), "r_w2": (r, r),
           "r_b2": (r,), "r_w3": (r, e),
           "e_gate": (e, h, f), "e_up": (e, h, f), "e_down": (e, f, h)}
    if i == 0:
        del out["r_gamma"]
    return out


def top_shapes(cfg: dict) -> dict:
    return {"embed": (cfg["vocab_size"], cfg["hidden_size"]),
            "final_norm": (cfg["hidden_size"],)}


def _deviation(name: str, shape: tuple, std: float, gain: float) -> float:
    """The deviation a normal leaf is drawn at (the module docstring)."""
    if name == "w0":                        # [C, k0]: the taps of a channel
        return 1.0 / math.sqrt(shape[1])
    if name == "w1":                        # [g, out, in, k1]
        return 1.0 / math.sqrt(shape[2] * shape[3])
    if name in _ROUTER_MATRICES:
        return gain / math.sqrt(shape[0])
    if len(shape) == 1 or name == "b1":     # norms and biases
        return VECTOR_STD
    return std


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str, std: float, gain: float):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            if name in ("temp", "r_gamma"):
                lo, hi = (0.5, 2.0) if name == "temp" else (0.5, 1.5)
                x = jax.random.uniform(k, shape, jnp.float32, lo, hi)
            else:
                x = (1.0 if name.endswith("_norm") else 0.0) \
                    + _deviation(name, shape, std, gain) \
                    * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out
    return jax.jit(draw)


def _make(cfg: dict, shapes: dict, seed: int, stream: int,
          dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    return _drawer(tuple(shapes.items()), dtype,
                   float(cfg["assumed"].get("initializer_std", MATRIX_STD)),
                   float(cfg["assumed"]["router_mlp_gain"]))(key)


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding and final norm; the head is the embedding (tied), under
    ``head`` as the untied configurations have theirs."""
    out = _make(cfg, top_shapes(cfg), seed, 0, dtype)
    out["head"] = out["embed"].T
    return out


def make_layer(cfg: dict, seed: int, i: int, dtype: str = "float32") -> dict:
    return _make(cfg, layer_shapes(cfg, i), seed, 1 + i, dtype)


def make_weights(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """The whole tree at once: small configurations and tests only."""
    out = make_top(cfg, seed, dtype)
    out["layers"] = [make_layer(cfg, seed, i, dtype)
                     for i in range(cfg["num_layers"])]
    return out


def to_program(made: dict) -> dict:
    """A layer's leaves as ``CCAMoEDecoderLayer`` names and holds them: q
    and k, and the two value halves, side by side in one matrix each; the
    taps leading (``conv0_weight`` ``[k0, C]``, ``conv1_weight`` ``[k1, g,
    in, out]``)."""
    import jax.numpy as jnp

    side = lambda a, b: jnp.concatenate([made.pop(a), made.pop(b)], axis=1)
    out = {"self_attn.qk_down.weight": side("w_q", "w_k"),
           "self_attn.v_proj.weight": side("w_v1", "w_v2"),
           "self_attn.conv0_weight": made.pop("w0").T,
           "self_attn.conv1_weight": made.pop("w1").transpose(3, 0, 2, 1),
           "self_attn.conv1_bias": made.pop("b1").reshape(-1)}
    for ours, theirs in _LAYER_NAMES.items():
        if ours in made:
            out[theirs] = made.pop(ours)
    return out


_TOP_NAMES = {"embed": "word_embeddings.weight",
              "final_norm": "final_norm.weight"}
_LAYER_NAMES = {
    "in_norm": "input_norm.weight", "b0": "self_attn.conv0_bias",
    "temp": "self_attn.temp", "w_o": "self_attn.o_proj.weight",
    "post_norm": "post_norm.weight",
    "r_down": "moe.router.down.weight", "r_down_b": "moe.router.down.bias",
    "r_gamma": "moe.router.gamma", "r_norm": "moe.router.norm.weight",
    "r_w1": "moe.router.fc1.weight", "r_b1": "moe.router.fc1.bias",
    "r_w2": "moe.router.fc2.weight", "r_b2": "moe.router.fc2.bias",
    "r_w3": "moe.router.out.weight", "e_gate": "moe.w_gate",
    "e_up": "moe.w_up", "e_down": "moe.w_down"}


def load_into(model, cfg: dict, seed: int) -> None:
    """Put the benchmark's weights into the program's model, a layer at a
    time: each leaf replaces the program's own before the next is made."""
    params = dict(model.named_parameters())
    dtype = cfg["weights_dtype"]

    def put(named: dict, prefix: str) -> None:
        for name in list(named):
            params.pop(prefix + name)._replace_value(named.pop(name))

    top = _make(cfg, top_shapes(cfg), seed, 0, dtype)
    put({theirs: top.pop(ours) for ours, theirs in _TOP_NAMES.items()}, "")
    for i in range(cfg["num_layers"]):
        made = make_layer(cfg, seed, i, dtype)
        named = to_program(made)
        if made:
            raise RuntimeError("the program has no place for %s"
                               % sorted(made))
        put(named, "layers.%d." % i)
    if params:
        raise RuntimeError("the benchmark has no weights for %s"
                           % sorted(params))
