"""The comparison that decides ``correct``: what the timed path produced,
at the timed sizes, against the plain reference.  Every number compared
has a limit of its own, read from the configuration's file.

Serving: for a seeded sample of the requests the window finished, the
longest among them, the reference runs once over each prompt with its
served tokens; compared are the widest and the mean gap by which a served
token's reference logit lies below the reference's best at that position,
and the bytes the program stored in an element type the configuration does
not state (``device.storage_census``), which have to be 0.

Training: the reference follows the first steps from the same seeded
weights and batches; compared are each step's loss, the norm of the first
gradient as the optimizer got it and the norm of the parameters' change,
both by the worst leaf: the gap between the program's norm and the
reference's, against the reference's norm of that leaf or of the median
leaf, whichever is larger.
"""
from __future__ import annotations

import statistics
import sys

import numpy as np

from . import reference, weights
from .serve import model_sizes

REF_LENGTHS = (512, 1024, 2048)     # one compile of the layer each


def sample_finished(records: list, seed: int, count: int) -> list:
    """``count`` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them; every one of them where
    ``count`` is 0."""
    done = [r for r in records if r["status"] == "ok"]
    if not done or not count:
        return done
    longest = max(done, key=lambda r: (r["prompt_tokens"] + len(r["tokens"]),
                                       r["index"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 6])
    picked = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(picked)]


def served_gaps(cfg: dict, seed: int, schedule, sample: list,
                mode: str = "float32", controls: tuple = (),
                w: dict = None) -> dict:
    """Reference logits over every sampled request.  Returns the gaps of
    the served tokens and, for each precision in ``controls``, of the
    tokens that precision puts first at the same positions."""
    import jax.numpy as jnp

    import time
    sizes = model_sizes(cfg)
    t0 = time.perf_counter()
    w = w or weights.make_weights(sizes, seed)
    t_weights = time.perf_counter() - t0
    gaps, control_gaps, agree = [], {c: [] for c in controls}, 0
    rows = reference.ROWS
    for r in sample:
        prompt = schedule.token_ids(r["index"], r["prompt_tokens"],
                                    cfg["vocab_size"])
        toks = r["tokens"][:rows]
        n, first = len(toks), len(prompt) - 1
        seq = prompt + toks[:-1]
        length = min(m for m in REF_LENGTHS if m >= first + rows)
        ids = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
        served = jnp.asarray(toks + [0] * (rows - n), jnp.int32)
        logits = reference.logits_rows(w, ids, first, sizes["num_heads"],
                                       mode)
        gap, same = reference.gaps_below_best(logits, served)
        gaps += np.asarray(gap)[:n].tolist()
        agree += int(np.asarray(same)[:n].sum())
        for c in controls:
            low = reference.logits_rows(w, ids, first, sizes["num_heads"],
                                        c)
            gap, _ = reference.gaps_below_best(logits,
                                               jnp.argmax(low, axis=-1))
            control_gaps[c] += np.asarray(gap)[:n].tolist()
    print("[reference] weights %.1f s, %d requests %.1f s"
          % (t_weights, len(sample), time.perf_counter() - t0 - t_weights),
          flush=True)
    return {"gaps": gaps, "control_gaps": control_gaps, "agree": agree}


def compare_serving(cfg, seed, schedule, records, count, storage) -> dict:
    sample = sample_finished(records, seed, count)
    if not sample:
        return {"requests_checked": {"value": 0, "limit": 1,
                                     "ok": False}}
    got = served_gaps(cfg, seed, schedule, sample)
    return serving_numbers(got["gaps"], got["agree"], storage,
                           cfg["limits"])


def serving_numbers(gaps: list, agree: int, storage: dict,
                    lim: dict) -> dict:
    """The numbers a serving run is held to, from the gaps of its served
    tokens and the census of what it stored.  The control's readings go
    through here too (``calibrate.py``, ``tests/test_correct.py``)."""
    return {
        "logit_gap_max": _held(max(gaps), lim["logit_gap_max"]),
        "logit_gap_mean": _held(sum(gaps) / len(gaps),
                                lim["logit_gap_mean"]),
        "unstated_storage_bytes": _held(storage["unstated_bytes"],
                                        lim["unstated_storage_bytes"]),
        "tokens_checked": {"value": len(gaps), "limit": 1,
                           "ok": len(gaps) >= 1},
        "argmax_agree_share": {"value": agree / len(gaps),
                               "limit": None, "ok": True},
    }


def _held(value: float, limit: float) -> dict:
    ok = bool(np.isfinite(value)) and value <= limit
    return {"value": float(value), "limit": limit, "ok": ok}


def worst_leaf(program: list, ref: list):
    """Over leaves, |program - ref| / max(ref leaf, median ref leaf);
    returns ``(largest, its index, median)``."""
    floor = statistics.median(ref)
    rel = [abs(p - r) / max(r, floor) for p, r in zip(program, ref)]
    i = int(np.argmax(rel))
    return rel[i], i, statistics.median(rel)


def reference_training(cfg, traffic, seed, host_batches, mode="float32"):
    """The reference's first steps, the batch in blocks of rows so that
    float32 activations fit."""
    import jax

    sizes = model_sizes(cfg)
    rows = traffic["reference_block_rows"]
    batches = []
    for ids, labels in host_batches[:traffic["checked_steps"]]:
        blocks = [(jax.device_put(ids[r:r + rows]),
                   jax.device_put(labels[r:r + rows]))
                  for r in range(0, ids.shape[0], rows)]
        batches.append((blocks, float(max(1, (labels != -100).sum()))))
    make = lambda: weights.make_weights(sizes, seed)
    return reference.train_steps(make(), batches, sizes["num_heads"],
                                 sizes["causal"], mode, cfg["optimizer"],
                                 make)


def compare_training(cfg, traffic, seed, got) -> dict:
    losses, first, change = reference_training(
        cfg, traffic, seed, got["host_batches"])
    lim = cfg["limits"]
    out = {}
    for i, (p, r) in enumerate(zip(got["losses"], losses), 1):
        out["loss_step%d_rel" % i] = _held(abs(p - r) / abs(r),
                                           lim["loss_rel"])
    g, gi, _ = worst_leaf(got["first_grad"], first)
    c, ci, c_median = worst_leaf(got["change"], change)
    names = weights.leaf_names(weights.from_program(
        model_sizes(cfg), lambda n: 0))
    out["first_grad_norm_worst_leaf"] = dict(_held(g, lim["first_grad_rel"]),
                                             leaf=names[gi])
    # AdamW divides by the gradient's own size, so a leaf whose gradient is
    # all but zero (a key bias: softmax does not see it) moves by the sign
    # of rounding noise; the median leaf is compared, the worst one shown
    out["param_change_norm_median_leaf"] = _held(c_median, lim["change_rel"])
    out["param_change_norm_worst_leaf"] = {"value": c, "limit": None,
                                           "ok": True, "leaf": names[ci]}
    return out


def verdict(compared: dict) -> bool:
    return bool(compared) and all(v["ok"] for v in compared.values())


def report(compared: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, v in compared.items():
        print("compared %s = %.6g (limit %s)%s%s"
              % (name, v["value"], v["limit"],
                 "" if v["ok"] else "  NOT HELD",
                 "  leaf %s" % v["leaf"] if "leaf" in v else ""),
              file=sys.stderr, flush=True)
