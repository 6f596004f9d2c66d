"""The comparison that decides ``correct`` for a block-diffusion serving
run: every state the program's denoising steps went through, replayed by
the plain reference in float32, a layer at a time.

The terminal line of a request carries, per served token, the denoising
step of its block that committed it (``commit_steps``).  From the prompt,
the tokens and those steps the reference lays out ONE forward holding the
clean sequence and every noisy (block, step) state (``blockdiff_reference
.replay_rows``) and the comparison holds, over a seeded sample of the
finished requests, the longest always among them:

- ``logit_gap_max`` / ``logit_gap_mean``: how far a served token's
  reference logit lies below the reference's best at that position IN THE
  STATE THAT COMMITTED IT.  Later blocks read the K/V the store pass wrote,
  so a store that is wrong or skipped shows in their gaps;
- ``confidence_gap_mean``: by how much the least confident position the
  program committed lies below the most confident one it left masked, in
  the reference's confidence (log of the largest softmax probability) at
  that state, 0 where the order agrees, averaged over the states: the
  choice of positions.  The widest such gap (``confidence_gap_max``) is
  shown and held to nothing: with seeded random weights it is one state's
  luck, and what a right and a wrong rule read overlap (PERF.md);
- ``unstated_storage_bytes``: 0, as for every serving configuration.
"""
from __future__ import annotations

import time

import numpy as np

from . import blockdiff_reference as ref, blockdiff_weights as bw
from .correct import _held, sample_finished

ROW_STEP = 512          # replays are padded to a multiple: few shapes,
                        # and the persistent compile cache finds them again


def _padded(n: int) -> int:
    return -(-n // ROW_STEP) * ROW_STEP


def replay_logits(cfg: dict, seed: int, replays: list, mode: str,
                  layer_of=None) -> list:
    """Reference logits ``[noisy rows, V]`` of every replay, the layers
    outermost so that one layer's float32 weights are held at a time.
    ``layer_of(i)`` gives layer ``i``'s weights (default: made from the
    seed)."""
    import jax
    import jax.numpy as jnp

    sizes = bw.sizes(cfg)
    key = ref.sizes_key(sizes)
    layer_of = layer_of or (lambda i: bw.make_layer(cfg, seed, i))
    top = bw.make_top(cfg, seed)
    hs = [top["embed"][jnp.asarray(r["ids"])].astype(jnp.float32)
          for r in replays]
    dev = [(jnp.asarray(r["pos"]), jnp.asarray(r["allow"]))
           for r in replays]
    for i in range(cfg["num_layers"]):
        p = layer_of(i)
        hs = [ref._layer_jit(h, p, pos, allow, key, mode)
              for h, (pos, allow) in zip(hs, dev)]
        jax.block_until_ready(hs)
        del p
    out = []
    for h, r in zip(hs, replays):
        # the noisy rows alone, padded like the replays themselves
        want = r["rows"] - r["clean_rows"]
        at = jnp.minimum(r["clean_rows"] + jnp.arange(_padded(want)),
                         h.shape[0] - 1)
        out.append(ref.head_logits(h[at], top["final_norm"], top["head"],
                                   sizes["norm_eps"], mode)[:want])
    return out


def request_replays(cfg: dict, schedule, sample: list) -> list:
    sizes = bw.sizes(cfg)
    out = []
    for r in sample:
        prompt = schedule.token_ids(r["index"], r["prompt_tokens"],
                                    cfg["vocab_size"])
        steps = (r["final"] or {}).get("commit_steps")
        if steps is None or len(steps) != len(r["tokens"]):
            raise RuntimeError(
                "request %s: the terminal line has no commit step for "
                "each of its %d tokens" % (r["index"], len(r["tokens"])))
        out.append(ref.replay_rows(prompt, r["tokens"], steps, sizes))
    # every replay at the longest one's length: one compile of the layer
    length = _padded(max(len(r["ids"]) for r in out))
    return [dict(ref.pad_rows(r, length), rows=len(r["ids"])) for r in out]


def readings(replays: list, logits: list, chosen=None, low=None) -> dict:
    """The gaps of every state of every replay.  ``chosen(state, block's
    logits, block's logits in ``low``)`` replaces the program's choice
    (a control's); ``low`` holds another precision's logits, replay for
    replay."""
    gaps, conf_gaps, agree = [], [], 0
    for n, (rep, lg) in enumerate(zip(replays, logits)):
        bl = rep["block_length"]
        for st in rep["states"]:
            at = st["offset"] - rep["clean_rows"]
            block = lg[at:at + bl]
            if chosen is not None:
                st = chosen(st, block,
                            None if low is None else low[n][at:at + bl])
            got = ref.state_readings(block, st)
            gaps += got["gaps"]
            agree += got["agree"]
            conf_gaps.append(got["confidence_gap"])
    return {"gaps": gaps, "confidence_gaps": conf_gaps, "agree": agree}


def _choice(st: dict, by_logits, reverse: bool = False) -> dict:
    """As many of a state's open positions as the program committed, in the
    order of ``by_logits``' confidence (the least confident first when
    ``reverse``), each with ``by_logits``' argmax token."""
    lg = np.asarray(by_logits)
    conf = np.asarray(ref.confidence(by_logits))
    open_ = [i for i, _ in st["committed"]] + st["masked_after"]
    order = sorted(open_,
                   key=lambda i: (conf[i] if reverse else -conf[i], i))
    n = len(st["committed"])
    return {"committed": [(i, int(lg[i].argmax())) for i in order[:n]],
            "masked_after": order[n:]}


def lower_precision_choice(st, _block, low):
    """What a lower precision would have committed: the positions ITS
    confidence puts first, each with ITS argmax token."""
    return _choice(st, low)


def reversed_choice(st, block, _low):
    """A wrong rule: the LEAST confident open positions, by the reference
    itself."""
    return _choice(st, block, reverse=True)


def serving_numbers(got: dict, storage: dict, lim: dict) -> dict:
    """The numbers a run is held to; the control's readings go through
    here too (``tests``, ``calibrate_blockgen.py``)."""
    gaps, conf = got["gaps"], got["confidence_gaps"]
    if not gaps:
        return {"tokens_checked": {"value": 0, "limit": 1, "ok": False}}
    return {
        "logit_gap_max": _held(max(gaps), lim["logit_gap_max"]),
        "logit_gap_mean": _held(sum(gaps) / len(gaps),
                                lim["logit_gap_mean"]),
        # over the states, a state whose order agrees counting 0: the mean
        # is held, the widest is one state's luck and is only shown
        "confidence_gap_mean": _held(sum(conf) / len(conf),
                                     lim["confidence_gap_mean"]),
        "confidence_gap_max": {"value": max(conf), "limit": None,
                               "ok": True},
        "unstated_storage_bytes": _held(storage["unstated_bytes"],
                                        lim["unstated_storage_bytes"]),
        "tokens_checked": {"value": len(gaps), "limit": 1, "ok": True},
        "argmax_agree_share": {"value": got["agree"] / len(gaps),
                               "limit": None, "ok": True},
    }


def compare_serving(cfg, seed, schedule, records, count, storage,
                    controls: tuple = ()) -> dict:
    sample = sample_finished(records, seed, count)
    if not sample:
        return {"requests_checked": {"value": 0, "limit": 1, "ok": False}}
    t0 = time.perf_counter()
    replays = request_replays(cfg, schedule, sample)
    logits = replay_logits(cfg, seed, replays, "float32")
    out = serving_numbers(readings(replays, logits), storage,
                          cfg["limits"])
    print("[reference] %d requests, %d rows, %.1f s"
          % (len(sample), sum(r["rows"] for r in replays),
             time.perf_counter() - t0), flush=True)
    for mode in controls:
        low = replay_logits(cfg, seed, replays, mode)
        ctl = serving_numbers(
            readings(replays, logits, lower_precision_choice, low),
            storage, cfg["limits"])
        for name in ("logit_gap_max", "logit_gap_mean",
                     "confidence_gap_mean", "confidence_gap_max"):
            out["control_%s_%s" % (mode, name)] = {
                "value": ctl[name]["value"], "limit": None, "ok": True}
        out["control_%s_fails" % mode] = {
            "value": float(not all(v["ok"] for v in ctl.values())),
            "limit": None, "ok": True}
    if controls:
        # and what a wrong RULE reads: the least confident positions
        # committed first, by the reference's own confidence
        rev = readings(replays, logits, reversed_choice)["confidence_gaps"]
        for name, value in (("max", max(rev)), ("mean", sum(rev) / len(rev))):
            out["control_reversed_confidence_gap_" + name] = {
                "value": value, "limit": None, "ok": True}
    return out
