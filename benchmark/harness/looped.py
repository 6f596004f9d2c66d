"""The looped decoder's serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``GenerationPool``, a decoder
whose stack runs several times on shared weights and whose every layer keeps
one K/V plane a pass under one block table.

The loop is ``serve.py``'s, imported as ``mamba.py`` imports it, and the
comparison ``retention_correct.compare_serving`` as it is, with this model's
``served_logits`` standing in for brumby's (the passes outermost, a layer's
float32 weights made once a pass) and the census of the K/V planes for the
census of a float32 state."""
from __future__ import annotations

from . import looped_costs, looped_reference as ref, looped_weights as lw, \
    serve, weights
from .blockgen import _standing_in
from .correct import _held
from .retention_correct import _padded


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 5.3 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import LoopedLM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = LoopedLM(**lw.model_kwargs(cfg))
    model.eval()
    lw.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build.  ``controls``
    (``benchmark/calibrate_looped.py``, the tests) makes ``compare`` read
    those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        return serve.measure(run, jax)


summarize = serve.summarize


def served_logits(cfg: dict, seed: int, sequences: list, mode: str) -> list:
    """Reference logits ``[served rows, V]`` of every ``(prompt, tokens)``
    under ``mode``: every request padded to the longest one's length (one
    compile of the layer), the passes outermost."""
    import jax.numpy as jnp

    sizes = lw.sizes(cfg)
    top = lw.make_top(cfg, seed)
    length = _padded(max(len(p) + len(t) - 1 for p, t in sequences))
    rows = _padded(max(len(t) for _, t in sequences))
    streams = []
    for prompt, toks in sequences:
        seq = list(prompt) + list(toks[:-1])
        ids = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
        streams.append(top["embed"][ids].astype(jnp.float32))
    hidden = ref.exit_hidden(
        streams, top, lambda l: lw.make_layer(cfg, seed, l),
        cfg["num_layers"], jnp.arange(length), sizes, mode)
    out = []
    for h, (prompt, toks) in zip(hidden, sequences):
        at = jnp.minimum(len(prompt) - 1 + jnp.arange(rows), length - 1)
        out.append(ref.head_logits(h[at], top["head"], mode)[:len(toks)])
    return out


def planes_held(cfg: dict, storage: dict) -> dict:
    """By how many bytes the arrays the program holds in the cache's type
    fall short of EVERY plane of the block pool (blocks x positions x
    layers x passes x K and V), held to 0: a program that kept one plane a
    layer would hold a quarter."""
    e = cfg["engine"]
    need = e["num_blocks"] * e["block_size"] \
        * looped_costs.kv_bytes_per_position(cfg)
    return _held(max(0, need - storage["by_type"].get(e["cache_dtype"], 0)),
                 0)


def compare(run, got) -> dict:
    from . import retention_correct
    with _standing_in(retention_correct, served_logits=served_logits,
                      state_held_in_float32=planes_held):
        out = retention_correct.compare_serving(
            run["cfg"], run["seed"], got["schedule"], got["records"],
            run["traffic"]["check_requests"], got["storage"],
            controls=run.get("controls", ()))
    # what ``compare_serving`` files under brumby's name is this model's
    # census of its K/V planes
    if "float32_state_bytes_short" in out:
        out["kv_planes_bytes_short"] = out.pop("float32_state_bytes_short")
    return out
