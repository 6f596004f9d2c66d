"""The window / global sparse-expert decoder's serving cells: ``POST
/generate`` on ``ServingHTTPFrontend`` -> ``ServingEngine`` ->
``GenerationPool``, a decoder whose window layers keep their K/V on a ring
of blocks beside global layers on the block table.

The loop is ``serve.py``'s, imported as ``mamba.py`` imports it, and the
comparison ``retention_correct.compare_serving`` as it is, with this model's
``served_logits`` standing in for brumby's (a layer's float32 weights made
once for every request, the band a mask over blocks of queries) and the
census of the two pools for the census of a float32 state."""
from __future__ import annotations

from . import device, serve, weights, window_moe_costs as costs, \
    window_moe_reference as ref, window_moe_weights as ww
from .blockgen import _standing_in
from .retention_correct import _padded


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 11.1 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import WindowMoELM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = WindowMoELM(**ww.model_kwargs(cfg))
    model.eval()
    ww.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build, and a second census of
    what is stored once the engine has stopped: ``serve.measure`` takes its
    own while the loop thread may still hold a prefill's row cache, and
    the pools are held to the byte.  ``controls``
    (``benchmark/calibrate_window_moe.py``, the tests) makes ``compare``
    read those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        got = serve.measure(run, jax)
    got["storage_settled"] = device.storage_census(jax,
                                                   run["cfg"]["storage"])
    serve.say("[storage settled] %s" % (got["storage_settled"],))
    return got


summarize = serve.summarize


def served_logits(cfg: dict, seed: int, sequences: list, mode: str) -> list:
    """Reference logits ``[served rows, V]`` of every ``(prompt, tokens)``
    under ``mode``: every request padded to the longest one's length (one
    compile of each kind of layer), the layers outermost."""
    import jax
    import jax.numpy as jnp

    sizes = ww.sizes(cfg)
    key = ref.sizes_key(sizes)
    top = ww.make_top(cfg, seed)
    length = _padded(max(len(p) + len(t) - 1 for p, t in sequences))
    rows = _padded(max(len(t) for _, t in sequences))
    pos = jnp.arange(length)
    hs = []
    for prompt, toks in sequences:
        seq = list(prompt) + list(toks[:-1])
        ids = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
        hs.append(top["embed"][ids].astype(jnp.float32))
    for i, (windowed, turned) in enumerate(zip(*ww.layouts(cfg))):
        p = ww.make_layer(cfg, seed, i)
        hs = [ref._layer_jit(h, p, pos, key, mode, bool(windowed),
                             bool(turned)) for h in hs]
        jax.block_until_ready(hs)
        del p
    out = []
    for h, (prompt, toks) in zip(hs, sequences):
        at = jnp.minimum(len(prompt) - 1 + jnp.arange(rows), length - 1)
        out.append(ref.head_logits(h[at], top["final_norm"], top["head"],
                                   sizes["norm_eps"], mode)[:len(toks)])
    return out


def pools_held(cfg: dict, storage: dict) -> dict:
    """By how many bytes what the program holds in the cache's type, once
    the engine has stopped, differs from the weights' arrays plus the
    global entries' pool plus the window entries' pool, SHORT OR OVER, held
    to 0: a program that kept its window layers on full-length pools holds
    more, one that kept fewer planes holds less."""
    e = cfg["engine"]
    need = sum(costs.pool_bytes(cfg).values())
    if e["cache_dtype"] == cfg["weights_dtype"]:
        need += costs.weight_leaf_bytes(
            cfg, 2 if cfg["weights_dtype"] == "bfloat16" else 4,
            cfg["storage"]["min_array_bytes"])
    have = storage["by_type"].get(e["cache_dtype"], 0)
    return {"short": max(0, need - have), "over": max(0, have - need)}


def compare(run, got) -> dict:
    from . import retention_correct
    from .correct import _held

    with _standing_in(retention_correct, served_logits=served_logits,
                      state_held_in_float32=lambda cfg, storage: _held(0, 0)):
        out = retention_correct.compare_serving(
            run["cfg"], run["seed"], got["schedule"], got["records"],
            run["traffic"]["check_requests"], got["storage"],
            controls=run.get("controls", ()))
    # what ``compare_serving`` files under brumby's name is not this
    # model's: its census is of the two pools, both ways
    out.pop("float32_state_bytes_short", None)
    held = pools_held(run["cfg"], got.get("storage_settled",
                                          got["storage"]))
    out["cache_pool_bytes_short"] = _held(held["short"], 0)
    out["cache_pool_bytes_over"] = _held(held["over"], 0)
    return out
