"""The CCA / routed-expert decoder's serving cells: ``POST /generate`` on
``ServingHTTPFrontend`` -> ``ServingEngine`` -> ``GenerationPool``, a decoder
whose every layer owns two cache entries (paged K/V and a convolution state
of constant size) and whose router carries a state from layer to layer.

The loop is ``serve.py``'s, imported as ``mamba.py`` imports it.  This module
supplies the build and the comparison.  The comparison is ``retention_correct
.compare_serving``'s statistic and sampling, written out here for one reason:
a request's logits over 262,272 rows of vocabulary are 2 GB, so they are
taken a block of served positions at a time and reduced to gaps at once
(``retention_correct.served_logits`` keeps every request's), and the layers
hand on two streams."""
from __future__ import annotations

import time

import numpy as np

from . import cca_reference as ref, cca_weights as cw, serve, weights
from .blockgen import _standing_in
from .correct import _held, sample_finished, serving_numbers
from .reference import gaps_below_best

ROW_STEP = 512


def build(cfg: dict, seed: int):
    """The model and engine with the benchmark's weights.  The program
    initialises its own 9.4 GB first and the benchmark replaces them a
    layer at a time, so the two sets are never on the device together."""
    import paddle_tpu as pt
    from paddle_tpu.models import CCAMoELM
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    pt.seed(weights.seed32(seed))
    model = CCAMoELM(**cw.model_kwargs(cfg))
    model.eval()
    cw.load_into(model, cfg, seed)
    engine = ServingEngine(model, max_len=cfg["max_len"], **cfg["engine"])
    return model, engine, ServingHTTPFrontend(engine)


def measure(run, jax, controls: tuple = ()) -> dict:
    """``serve.measure`` with this module's build.  ``controls``
    (``benchmark/calibrate_cca.py``, the tests) makes ``compare`` read
    those controls beside the reference."""
    run["controls"] = tuple(controls)
    with _standing_in(serve, build=build):
        return serve.measure(run, jax)


summarize = serve.summarize


def _padded(n: int) -> int:
    return -(-n // ROW_STEP) * ROW_STEP


def served_hidden(cfg: dict, seed: int, sequences: list, modes: tuple) -> dict:
    """``{mode: [hidden [T, H] of every (prompt, tokens)]}``: the layers
    outermost, a layer's float32 weights made once for every mode, every
    request padded to the longest one's length (one compile of the layer a
    mode)."""
    import jax
    import jax.numpy as jnp

    key = ref.sizes_key(cw.sizes(cfg))
    embed = cw.make_top(cfg, seed)["embed"]
    length = _padded(max(len(p) + len(t) - 1 for p, t in sequences))
    pos = jnp.arange(length)
    first = []
    for prompt, toks in sequences:
        seq = list(prompt) + list(toks[:-1])
        ids = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
        first.append((embed[ids].astype(jnp.float32), None))
    del embed
    streams = {mode: list(first) for mode in modes}
    for i in range(cfg["num_layers"]):
        p = cw.make_layer(cfg, seed, i)
        for mode in modes:
            streams[mode] = [ref._layer_jit(h, r, p, pos, key, mode)
                             for h, r in streams[mode]]
        jax.block_until_ready(streams)
        del p
    return {mode: [h for h, _ in hs] for mode, hs in streams.items()}


def served_gaps(cfg: dict, seed: int, sequences: list, hidden: dict) -> dict:
    """``{mode: {"gaps", "agree"}}`` in the float32 reference's logits: of
    the served tokens under ``float32``, of the tokens a control puts first
    under its name.  A block of ``ROW_STEP`` served positions at a time."""
    import jax.numpy as jnp

    top = cw.make_top(cfg, seed)
    eps = cw.sizes(cfg)["norm_eps"]
    out = {mode: {"gaps": [], "agree": 0} for mode in hidden}

    def logits(mode, i, at):
        return ref.head_logits(hidden[mode][i][at], top["final_norm"],
                               top["head"], eps, ref.head_mode(mode))

    for i, (prompt, toks) in enumerate(sequences):
        last = hidden["float32"][i].shape[0] - 1
        for start in range(0, len(toks), ROW_STEP):
            n = min(ROW_STEP, len(toks) - start)
            at = jnp.minimum(len(prompt) - 1 + start + jnp.arange(ROW_STEP),
                             last)
            lg = logits("float32", i, at)
            served = list(toks[start:start + n]) + [0] * (ROW_STEP - n)
            for mode in hidden:
                chosen = jnp.asarray(served, jnp.int32) \
                    if mode == "float32" \
                    else jnp.argmax(logits(mode, i, at), axis=-1)
                gap, same = gaps_below_best(lg, chosen)
                out[mode]["gaps"] += np.asarray(gap)[:n].tolist()
                out[mode]["agree"] += int(np.asarray(same)[:n].sum())
    return out


def state_held(cfg: dict, storage: dict) -> dict:
    """By how many bytes the arrays the program holds in the weights' type
    fall short of the CCA state of every slot and layer (the last latents,
    the first convolution's last outputs, the next position's value half),
    held to 0."""
    s = cw.sizes(cfg)
    width = (s["num_heads"] + s["num_kv_heads"]) * s["head_dim"]
    per_layer = (s["k0"] - 1 + s["k1"] - 1) * width \
        + s["num_kv_heads"] * s["head_dim"] // 2
    import jax.numpy as jnp

    need = cfg["engine"]["slots"] * cfg["num_layers"] * per_layer \
        * jnp.dtype(cfg["weights_dtype"]).itemsize
    return _held(max(0, need - storage["by_type"].get(cfg["weights_dtype"],
                                                      0)), 0)


def compare(run, got) -> dict:
    cfg, seed, controls = run["cfg"], run["seed"], run.get("controls", ())
    sample = sample_finished(got["records"], seed,
                             run["traffic"]["check_requests"])
    if not sample:
        return {"requests_checked": {"value": 0, "limit": 1, "ok": False}}
    t0 = time.perf_counter()
    sequences = [(got["schedule"].token_ids(r["index"], r["prompt_tokens"],
                                            cfg["vocab_size"]), r["tokens"])
                 for r in sample]
    read = served_gaps(cfg, seed, sequences, served_hidden(
        cfg, seed, sequences, ("float32",) + tuple(controls)))
    numbers = {mode: serving_numbers(r["gaps"], r["agree"], got["storage"],
                                     cfg["limits"])
               for mode, r in read.items()}
    out = numbers.pop("float32")
    out["cca_state_bytes_short"] = state_held(cfg, got["storage"])
    out["requests_checked"] = {"value": len(sample), "limit": 1, "ok": True}
    out["longest_checked"] = {
        "value": max(len(p) + len(t) for p, t in sequences), "limit": None,
        "ok": True}
    for mode, ctl in numbers.items():
        for name in ("logit_gap_max", "logit_gap_mean"):
            out["control_%s_%s" % (mode, name)] = {
                "value": ctl[name]["value"], "limit": None, "ok": True}
        out["control_%s_fails" % mode] = {
            "value": float(not all(v["ok"] for v in ctl.values())),
            "limit": None, "ok": True}
    print("[reference] %d requests, longest %d positions, controls %s, "
          "%.1f s" % (len(sample), out["longest_checked"]["value"],
                      list(controls), time.perf_counter() - t0), flush=True)
    return out
