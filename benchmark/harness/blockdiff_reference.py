"""Plain reference for a Qwen3-style sparse-expert decoder that generates by
diffusion over blocks (SDAR, ``model_type: sdar_moe``): the forward in
straightforward ``jax.numpy`` and the generation rule as a plain loop.  No
kernel, no cache, no batching, the experts as a loop with masks, and
nothing imported from the program.

``mode`` as in ``reference.py``: ``float32`` is the reference proper
(float32 storage, matmuls at ``highest``); ``fp8`` is the control for a
configuration that states bfloat16 (matmul operands through float8_e4m3fn
with a per-tensor scale, everything else float32).

Weights: ``{"embed" [V, H], "final_norm" [H], "head" [H, V], "layers":
[{"in_norm", "wq" [H, Hq*D], "wk", "wv" [H, Hkv*D], "wo" [Hq*D, H],
"q_norm", "k_norm" [D], "post_norm" [H], "router" [H, E], "w_gate",
"w_up" [E, H, F], "w_down" [E, F, H]}]}``; ``sizes`` holds ``num_heads``,
``num_kv_heads``, ``head_dim``, ``top_k``, ``rope_theta``, ``norm_eps``,
``block_length``, ``mask_token_id``, ``denoise_steps``.

Departures from the released model, each also in the configuration file:

- the block length, the mask id, the number of denoising steps and the
  remasking rule are the release's generation defaults as this benchmark
  reads them (``assumed``), not keys of its ``config.json``;
- a step commits a FIXED number of positions (``commit_plan``), the most
  confident first, ties to the earlier position; the release can also stop
  a block early on a confidence threshold, which seeded random weights
  would never reach;
- a prompt's trailing partial block is denoised with its prompt tokens
  held fixed, and a last block fills only the positions asked for, the
  rest staying mask ids;
- the confidence is compared as the log of the largest softmax
  probability (the same order as the probability itself).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference import _mm

NEG = float(np.finfo(np.float32).min)


def commit_plan(to_fill: int, denoise_steps: int) -> list:
    """Positions each denoising step of a block commits: ``to_fill`` over
    ``min(denoise_steps, to_fill)`` steps, the larger counts first."""
    steps = min(int(denoise_steps), int(to_fill))
    if steps < 1:
        return []
    base, extra = divmod(int(to_fill), steps)
    return [base + (1 if t < extra else 0) for t in range(steps)]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def _rotary(x, pos, theta):
    """``x`` [L, n, D] at positions ``pos`` [L], halves rotated."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                           / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(h, p, pos, allow, sizes, mode: str):
    """One layer on ``h`` [L, H] float32; row ``i`` stands at position
    ``pos[i]`` and attends row ``j`` where ``allow[i, j]``."""
    n, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    eps, l = sizes["norm_eps"], h.shape[0]
    a = _rms(h, p["in_norm"], eps)
    q = _mm(a, p["wq"], mode).reshape(l, n, d)
    k = _mm(a, p["wk"], mode).reshape(l, kv, d)
    v = _mm(a, p["wv"], mode).reshape(l, kv, d)
    q = _rotary(_rms(q, p["q_norm"], eps), pos, sizes["rope_theta"])
    k = _rotary(_rms(k, p["k_norm"], eps), pos, sizes["rope_theta"])
    # query head n on K/V head n // g: the K/V heads repeated g times
    kr = jnp.repeat(k, n // kv, axis=1).transpose(1, 0, 2)      # [n, L, D]
    vr = jnp.repeat(v, n // kv, axis=1).transpose(1, 0, 2)
    s = _mm(q.transpose(1, 0, 2), kr.transpose(0, 2, 1), mode) \
        / math.sqrt(d)
    w = jax.nn.softmax(jnp.where(allow[None], s, NEG), axis=-1)
    o = _mm(w, vr, mode).transpose(1, 0, 2).reshape(l, n * d)
    h = h + _mm(o, p["wo"], mode)
    m = _rms(h, p["post_norm"], eps)
    probs = jax.nn.softmax(_mm(m, p["router"], mode), axis=-1)
    top, chosen = jax.lax.top_k(probs, sizes["top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)

    def one_expert(acc, e_w):
        e, wg, wu, wd = e_w
        gate = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        y = _mm(jax.nn.silu(_mm(m, wg, mode)) * _mm(m, wu, mode), wd, mode)
        return acc + gate[:, None] * y, None

    experts = jnp.arange(p["w_gate"].shape[0])
    moe, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (experts, p["w_gate"], p["w_up"], p["w_down"]))
    return h + moe


def block_causal(length: int, block_length: int):
    blk = np.arange(length) // block_length
    return jnp.asarray(blk[None, :] <= blk[:, None])


def sizes_key(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


@functools.partial(jax.jit, static_argnames=("sizes", "mode"))
def _layer_jit(h, p, pos, allow, sizes, mode):
    return layer_forward(h, p, pos, allow, dict(sizes), mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head_logits(h, final_norm, head, eps, mode):
    return _mm(_rms(h, final_norm, eps), head, mode)


def forward_logits(weights, ids, sizes, mode: str = "float32",
                   pos=None, allow=None):
    """Logits [L, V] of the rows ``ids`` [L]: by default one sequence from
    position 0 under the block-causal mask."""
    ids = jnp.asarray(ids, jnp.int32)
    l = ids.shape[0]
    pos = jnp.arange(l) if pos is None else jnp.asarray(pos)
    if allow is None:
        allow = block_causal(l, sizes["block_length"])
    h = weights["embed"][ids].astype(jnp.float32)
    for p in weights["layers"]:
        h = _layer_jit(h, p, pos, allow, sizes_key(sizes), mode)
    return head_logits(h, weights["final_norm"], weights["head"],
                       sizes["norm_eps"], mode)


def confidence(logits):
    """Log of the largest softmax probability, per row."""
    return jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)


def choose(conf, masked, count: int):
    """The ``count`` masked positions of largest confidence, ties to the
    earlier one: a boolean vector."""
    order = sorted((i for i in range(len(masked)) if masked[i]),
                   key=lambda i: (-float(conf[i]), i))
    out = np.zeros(len(masked), bool)
    out[order[:count]] = True
    return out


def generate(weights, prompt, max_new: int, sizes, mode: str = "float32"):
    """The generation rule as a plain loop, a full forward every step.
    Returns ``(tokens, commit_steps)``: the tokens in position order and,
    for each, the denoising step of its block that committed it."""
    bl, mask_id = sizes["block_length"], sizes["mask_token_id"]
    seq = [int(t) for t in prompt]
    tokens, steps = [], []
    while len(tokens) < max_new:
        start = len(seq) // bl * bl
        fixed = seq[start:]
        fill = min(bl - len(fixed), max_new - len(tokens))
        block = fixed + [mask_id] * (bl - len(fixed))
        masked = np.zeros(bl, bool)
        masked[len(fixed):len(fixed) + fill] = True
        when = np.zeros(bl, np.int32)
        for t, count in enumerate(commit_plan(fill, sizes["denoise_steps"]),
                                  1):
            logits = forward_logits(weights, seq[:start] + block, sizes,
                                    mode)[start:]
            took = choose(np.asarray(confidence(logits)), masked, count)
            best = np.asarray(jnp.argmax(logits, axis=-1))
            for i in np.flatnonzero(took):
                block[i], when[i] = int(best[i]), t
            masked &= ~took
        new = block[len(fixed):len(fixed) + fill]
        tokens += new
        steps += when[len(fixed):len(fixed) + fill].tolist()
        seq = seq[:start] + block[:len(fixed) + fill]
    return tokens, steps


# -- replay: every state the program went through, in one masked forward ----

def replay_rows(prompt, tokens, commit_steps, sizes) -> dict:
    """The rows of ONE forward that holds a request's clean sequence and
    every noisy state of every block it generated, as block-diffusion
    training lays them out: a clean row sees the clean rows of its own and
    earlier blocks; a row of a noisy state sees the clean rows of earlier
    blocks and the rows of its own state.

    A state is (block, step ``t``): the block as the program's ``t``-th
    denoising step saw it, the prompt's tokens and those committed at
    steps before ``t`` in place, mask ids elsewhere.  Returns ``ids``,
    ``pos``, ``allow`` and ``states``: for each state its row offset, the
    block-local positions committed AT ``t`` with their served tokens, and
    those left masked after it."""
    bl, mask_id = sizes["block_length"], sizes["mask_token_id"]
    n = len(prompt)
    clean = [int(t) for t in prompt] + [int(t) for t in tokens]
    when = [0] * n + [int(s) for s in commit_steps]
    ids, pos, state_of = list(clean), list(range(len(clean))), \
        [0] * len(clean)
    states = []
    first = n // bl
    for b in range(first, (len(clean) + bl - 1) // bl):
        at = list(range(b * bl, (b + 1) * bl))
        have = [p < len(clean) for p in at]
        last = max([when[p] for p, ok in zip(at, have) if ok] or [0])
        for t in range(1, last + 1):
            sid = len(states) + 1
            seen = [ok and when[p] < t for p, ok in zip(at, have)]
            states.append({
                "offset": len(ids),
                "committed": [(i, clean[p]) for i, (p, ok)
                              in enumerate(zip(at, have))
                              if ok and when[p] == t],
                "masked_after": [i for i, (p, ok) in enumerate(zip(at, have))
                                 if ok and when[p] > t],
            })
            ids += [clean[p] if s else mask_id for p, s in zip(at, seen)]
            pos += at
            state_of += [sid] * bl
    pos_a, st = np.asarray(pos), np.asarray(state_of)
    blk = pos_a // bl
    clean_key = (st[None, :] == 0)
    allow = np.where(
        st[:, None] == 0,
        clean_key & (blk[None, :] <= blk[:, None]),
        (clean_key & (blk[None, :] < blk[:, None]))
        | (st[None, :] == st[:, None]))
    return {"ids": np.asarray(ids, np.int32), "pos": pos_a.astype(np.int32),
            "allow": allow, "states": states, "clean_rows": len(clean),
            "block_length": bl}


def pad_rows(rows: dict, length: int) -> dict:
    """``rows`` padded to ``length`` rows that nothing sees (one compiled
    shape for many requests); a padding row sees itself alone."""
    have = len(rows["ids"])
    if have > length:
        raise ValueError("replay of %d rows exceeds %d" % (have, length))
    extra = length - have
    allow = np.zeros((length, length), bool)
    allow[:have, :have] = rows["allow"]
    allow[np.arange(have, length), np.arange(have, length)] = True
    return dict(rows, ids=np.pad(rows["ids"], (0, extra)),
                pos=np.pad(rows["pos"], (0, extra)), allow=allow)


def state_readings(logits_of_state, state: dict) -> dict:
    """What one state's reference logits ``[block_length, V]`` say of what
    the program did there: for each position it committed, how far the
    served token's logit lies below the best (``gaps``) and whether it is
    the best (``agree``); and by how much the least confident committed
    position lies below the most confident one left masked
    (``confidence_gap``, 0 where none was left or the order agrees)."""
    conf = np.asarray(confidence(logits_of_state))
    lg = np.asarray(logits_of_state)
    gaps, agree = [], 0
    for i, tok in state["committed"]:
        gaps.append(float(lg[i].max() - lg[i, tok]))
        agree += int(lg[i].argmax() == tok)
    gap = 0.0
    if state["committed"] and state["masked_after"]:
        low = min(conf[i] for i, _ in state["committed"])
        high = max(conf[i] for i in state["masked_after"])
        gap = max(0.0, float(high - low))
    return {"gaps": gaps, "agree": agree, "confidence_gap": gap}
