"""Seeded weights for the hybrid Mamba / attention decoder (``jamba``), made
by the benchmark one layer at a time as ``retention_weights.py`` makes
brumby's: the float32 reference holds a layer at a time.  Every value of a
leaf the program keeps in bfloat16 is exactly representable in bfloat16, so
the program and the reference start from identical numbers; the leaves the
configuration states in float32 (the norms' scales, ``A_log``, ``D``,
``b_dt``) go to both sides as they are drawn.

Matrices are normal with deviation 0.02 and norm vectors 1 + 0.02 normal, as
in the other configurations.  THE RECURRENCE'S OWN PARAMETERS ARE NOT: with
``A_log`` and ``b_dt`` near 0 a state forgets in three tokens, and a
comparison through the cache would then say nothing of a carried state.
They take the Mamba release's own initialiser: ``A_log = log(1..N)`` a
channel, ``D = 1``, and ``b_dt`` the inverse softplus of a step drawn
log-uniformly in ``assumed.dt_init`` = 0.001 to 0.1, so that a channel's
slowest state remembers for 10 to 1,000 positions.  The convolution's
weights and bias are uniform in +-1 / sqrt(d_conv), the release's too (a
depthwise ``Conv1d``'s default): at deviation 0.02 the four taps would hand
the scan an input of 0.02 and the mixers would add a fiftieth of what the
MLPs add to the residual stream, so no fault of a mixer would reach a
logit.  Initialisers, listed under the configuration's ``assumed``.

The leaves have the reference's layout: ``A_log`` ``[C, N]`` as published
(the program holds it transposed, channels innermost), the convolution
``[K, C]``.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .weights import MATRIX_STD, VECTOR_STD, seed32

#: leaves the configuration states in float32 whatever ``weights_dtype``
FLOAT32_LEAVES = ("in_norm", "post_norm", "final_norm", "dt_norm", "b_norm",
                  "c_norm", "A_log", "D", "b_dt")


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def sizes(cfg: dict) -> dict:
    """What the reference needs of a configuration, under its own names."""
    return {"num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": head_dim(cfg),
            "d_inner": cfg["mamba_expand"] * cfg["hidden_size"],
            "d_state": cfg["mamba_d_state"], "d_conv": cfg["mamba_d_conv"],
            "dt_rank": cfg["mamba_dt_rank"],
            "norm_eps": float(cfg["rms_norm_eps"])}


def model_kwargs(cfg: dict) -> dict:
    """``HybridMambaLM``'s arguments from the configuration."""
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=head_dim(cfg),
                intermediate_size=cfg["intermediate_size"],
                attn_layer_period=cfg["attn_layer_period"],
                attn_layer_offset=cfg["attn_layer_offset"],
                mamba_expand=cfg["mamba_expand"],
                mamba_d_state=cfg["mamba_d_state"],
                mamba_d_conv=cfg["mamba_d_conv"],
                mamba_dt_rank=cfg["mamba_dt_rank"],
                norm_epsilon=float(cfg["rms_norm_eps"]),
                dtype=cfg["weights_dtype"])


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves that are drawn (normal) of layer ``i``."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    mlp = {"post_norm": (h,), "w_gate": (h, f), "w_up": (h, f),
           "w_down": (f, h)}
    if is_attention(cfg, i):
        d = head_dim(cfg)
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        return {"in_norm": (h,), "wq": (h, nq * d), "wk": (h, nkv * d),
                "wv": (h, nkv * d), "wo": (nq * d, h), **mlp}
    s = sizes(cfg)
    c, n, k, r = s["d_inner"], s["d_state"], s["d_conv"], s["dt_rank"]
    return {"in_norm": (h,), "w_in": (h, 2 * c), "conv_w": (k, c),
            "conv_b": (c,), "w_x": (c, r + 2 * n), "dt_norm": (r,),
            "b_norm": (n,), "c_norm": (n,), "w_dt": (r, c),
            "w_out": (c, h), **mlp}


def top_shapes(cfg: dict) -> dict:
    return {"embed": (cfg["vocab_size"], cfg["hidden_size"]),
            "final_norm": (cfg["hidden_size"],)}


@functools.lru_cache(maxsize=None)
def _drawer(shapes: tuple, dtype: str, std: float, d_conv: int):
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for (name, shape), k in zip(shapes,
                                    jax.random.split(key, len(shapes))):
            if name.startswith("conv_"):
                x = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) \
                    / math.sqrt(d_conv)
            else:
                norm = name.endswith("_norm")
                x = (1.0 if norm else 0.0) + (VECTOR_STD if len(shape) == 1
                                              else std) \
                    * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(jnp.bfloat16).astype(
                "float32" if name in FLOAT32_LEAVES else dtype)
        return out
    return jax.jit(draw)


def _make(cfg: dict, shapes: dict, seed: int, stream: int,
          dtype: str) -> dict:
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed, 1)), stream)
    std = float(cfg["assumed"].get("initializer_std", MATRIX_STD))
    return _drawer(tuple(shapes.items()), dtype, std,
                   cfg["mamba_d_conv"])(key)


def recurrence_leaves(cfg: dict, seed: int, layer: int) -> dict:
    """``A_log`` ``[C, N]``, ``D`` ``[C]`` and ``b_dt`` ``[C]``, float32, by
    the Mamba release's initialiser (the module docstring)."""
    s = sizes(cfg)
    c, n = s["d_inner"], s["d_state"]
    lo, hi = cfg["assumed"]["dt_init"]
    u = np.random.default_rng([seed32(seed, 2), layer]).random(c)
    dt = np.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo))
    return {"A_log": np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32)), (c, n)).copy(),
            "D": np.ones(c, np.float32),
            "b_dt": (dt + np.log(-np.expm1(-dt))).astype(np.float32)}


def make_top(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """Embedding and final norm; the head is the embedding (tied), under
    ``head`` as the untied configurations have theirs."""
    out = _make(cfg, top_shapes(cfg), seed, 0, dtype)
    out["head"] = out["embed"].T
    return out


def make_layer(cfg: dict, seed: int, i: int, dtype: str = "float32") -> dict:
    import jax.numpy as jnp

    out = _make(cfg, layer_shapes(cfg, i), seed, 1 + i, dtype)
    if not is_attention(cfg, i):
        out.update({k: jnp.asarray(v)
                    for k, v in recurrence_leaves(cfg, seed, i).items()})
    return out


def make_weights(cfg: dict, seed: int, dtype: str = "float32") -> dict:
    """The whole tree at once: small configurations and tests only."""
    out = make_top(cfg, seed, dtype)
    out["layers"] = [make_layer(cfg, seed, i, dtype)
                     for i in range(cfg["num_layers"])]
    return out


_TOP_NAMES = {"embed": "word_embeddings.weight",
              "final_norm": "final_norm.weight"}
_MLP_NAMES = {"in_norm": "input_norm.weight", "post_norm": "post_norm.weight",
              "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
              "w_down": "mlp.down_proj.weight"}
_ATTENTION_NAMES = {
    "wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
    "wv": "mixer.v_proj.weight", "wo": "mixer.out_proj.weight", **_MLP_NAMES}
_MAMBA_NAMES = {
    "w_in": "mixer.in_proj.weight", "conv_w": "mixer.conv_weight",
    "conv_b": "mixer.conv_bias", "w_x": "mixer.x_proj.weight",
    "dt_norm": "mixer.dt_norm.weight", "b_norm": "mixer.b_norm.weight",
    "c_norm": "mixer.c_norm.weight", "w_dt": "mixer.dt_proj.weight",
    "b_dt": "mixer.dt_bias", "A_log": "mixer.A_log", "D": "mixer.D",
    "w_out": "mixer.out_proj.weight", **_MLP_NAMES}


def load_into(model, cfg: dict, seed: int) -> None:
    """Put the benchmark's weights into the program's model, a layer at a
    time: each leaf replaces the program's own before the next is made.
    ``A_log`` goes in transposed (the program keeps channels innermost)."""
    params = dict(model.named_parameters())
    dtype = cfg["weights_dtype"]

    def put(made: dict, names: dict, prefix: str) -> None:
        for ours, theirs in names.items():
            leaf = made.pop(ours)
            params.pop(prefix + theirs)._replace_value(
                leaf.T if ours == "A_log" else leaf)

    top = make_top(cfg, seed, dtype)
    del top["head"]
    put(top, _TOP_NAMES, "")
    for i in range(cfg["num_layers"]):
        put(make_layer(cfg, seed, i, dtype),
            _ATTENTION_NAMES if is_attention(cfg, i) else _MAMBA_NAMES,
            "layers.%d." % i)
    if params:
        raise RuntimeError("the benchmark has no weights for %s"
                           % sorted(params))
