"""The comparison that decides ``correct`` for the power-retention decoder:
``correct.compare_serving``'s statistic (how far a served token's reference
logit lies below the reference's best at its position, widest and mean over
a seeded sample of the finished requests, the longest always among them, and
the bytes stored in an element type the configuration does not state), with
this model's reference and weights: ``correct.py`` calls the transformer's.

The reference runs once over each sampled request's prompt and served
tokens, a layer's float32 weights held at a time, every request padded to
the longest one's length (one compile of the layer).

``controls`` (calibration and tests only) reads, beside the reference, what
a faulty program would have served at the same positions: the tokens a
control puts first, their gaps taken in the reference's logits.  ``fp8``:
matmul operands through float8_e4m3fn; ``bf16_state``: the state held in
bfloat16 (``retention_reference.retention_recurrent``); ``no_gate``: the
decay dropped.
"""
from __future__ import annotations

import time

import numpy as np

from . import retention_reference as ref, retention_weights as rw
from .correct import _held, sample_finished, serving_numbers

ROW_STEP = 512


def _padded(n: int) -> int:
    return -(-n // ROW_STEP) * ROW_STEP


def served_logits(cfg: dict, seed: int, sequences: list, mode: str) -> list:
    """Reference logits ``[served rows, V]`` of every ``(prompt, tokens)``,
    the layers outermost."""
    import jax
    import jax.numpy as jnp

    sizes = rw.sizes(cfg)
    key = ref.sizes_key(sizes)
    top = rw.make_top(cfg, seed)
    length = _padded(max(len(p) + len(t) - 1 for p, t in sequences))
    rows = _padded(max(len(t) for _, t in sequences))
    pos = jnp.arange(length)
    hs, lens = [], []
    for prompt, toks in sequences:
        seq = list(prompt) + list(toks[:-1])
        ids = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
        hs.append(top["embed"][ids].astype(jnp.float32))
        lens.append(jnp.asarray(len(prompt), jnp.int32))
    for i in range(cfg["num_layers"]):
        p = rw.make_layer(cfg, seed, i)
        hs = [ref._layer_jit(h, p, pos, n, key, mode)
              for h, n in zip(hs, lens)]
        jax.block_until_ready(hs)
        del p
    out = []
    for h, (prompt, toks) in zip(hs, sequences):
        at = jnp.minimum(len(prompt) - 1 + jnp.arange(rows), length - 1)
        out.append(ref.head_logits(h[at], top["final_norm"], top["head"],
                                   sizes["norm_eps"],
                                   ref.head_mode(mode))[:len(toks)])
    return out


def gaps(logits: list, chosen: list) -> dict:
    """The gaps of ``chosen`` tokens below the reference's best."""
    import jax.numpy as jnp

    from .reference import gaps_below_best

    out, agree = [], 0
    for lg, toks in zip(logits, chosen):
        gap, same = gaps_below_best(lg, jnp.asarray(toks, jnp.int32))
        out += np.asarray(gap).tolist()
        agree += int(np.asarray(same).sum())
    return {"gaps": out, "agree": agree}


def state_held_in_float32(cfg: dict, storage: dict) -> dict:
    """By how many bytes the float32 arrays the program holds fall short of
    the retention state of every slot and layer (``retention_costs``), held
    to 0.  The logit gaps cannot tell a state held in bfloat16 from a sound
    one (PERF.md section 6, PR 32: what such a state moves is an eighth of
    what bfloat16 matmuls move), so the state's element type is held by
    the census of what is stored."""
    from . import retention_costs

    need = cfg["engine"]["slots"] * retention_costs.state_bytes_per_slot(cfg)
    have = storage["by_type"].get("float32", 0)
    return _held(max(0, need - have), 0)


def compare_serving(cfg, seed, schedule, records, count, storage,
                    controls: tuple = ()) -> dict:
    import jax.numpy as jnp

    sample = sample_finished(records, seed, count)
    if not sample:
        return {"requests_checked": {"value": 0, "limit": 1, "ok": False}}
    t0 = time.perf_counter()
    sequences = [(schedule.token_ids(r["index"], r["prompt_tokens"],
                                     cfg["vocab_size"]), r["tokens"])
                 for r in sample]
    logits = served_logits(cfg, seed, sequences, "float32")
    got = gaps(logits, [t for _, t in sequences])
    out = serving_numbers(got["gaps"], got["agree"], storage, cfg["limits"])
    out["float32_state_bytes_short"] = state_held_in_float32(cfg, storage)
    out["requests_checked"] = {"value": len(sample), "limit": 1, "ok": True}
    out["longest_checked"] = {
        "value": max(len(p) + len(t) for p, t in sequences), "limit": None,
        "ok": True}
    print("[reference] %d requests, longest %d positions, %.1f s"
          % (len(sample), out["longest_checked"]["value"],
             time.perf_counter() - t0), flush=True)
    for mode in controls:
        t1 = time.perf_counter()
        low = served_logits(cfg, seed, sequences, mode)
        ctl = gaps(logits, [np.asarray(jnp.argmax(x, axis=-1)) for x in low])
        ctl = serving_numbers(ctl["gaps"], ctl["agree"], storage,
                              cfg["limits"])
        for name in ("logit_gap_max", "logit_gap_mean"):
            out["control_%s_%s" % (mode, name)] = {
                "value": ctl[name]["value"], "limit": None, "ok": True}
        out["control_%s_fails" % mode] = {
            "value": float(not all(v["ok"] for v in ctl.values())),
            "limit": None, "ok": True}
        print("[control %s] %.1f s" % (mode, time.perf_counter() - t1),
              flush=True)
    return out



