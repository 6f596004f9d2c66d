"""Plain references: a pre-norm transformer LM in straightforward
``jax.numpy``, its masked-LM loss, gradients and AdamW.  No kernels, no
cache, no batching tricks, and nothing imported from the program.

``mode`` is the precision the arithmetic runs in:

- ``float32``  the reference proper: float32 storage, matmuls at
  ``highest`` precision (on a TPU a default float32 matmul is one bf16
  pass);
- ``bfloat16`` the control for a configuration that states float32:
  weights, activations and matmuls in bfloat16;
- ``fp8``      the control for a configuration that states bfloat16:
  matmul operands through float8_e4m3fn with a per-tensor scale,
  accumulation and everything else in float32.

Follows Vaswani et al. with the pre-norm placement GPT-2/3 use, learned
positions, exact (erf) GELU, LayerNorm epsilon 1e-5 and an output head tied
to the token embedding.  ``causal`` false gives the BERT encoder (no
segment embedding, no pooler, no MLM transform head: the departures the
program's ``TransformerLM`` makes from Devlin et al., kept here so that both
compute the same function).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
IGNORE = -100
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _store(x, mode):
    return x.astype(jnp.bfloat16) if mode == "bfloat16" else x


def _fake_quant(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q8(x):
    """The usual fp8 recipe: a matmul operand through e4m3 with a
    per-tensor scale on the way forward, its cotangent through e5m2 with a
    per-tensor scale on the way back."""
    return _fake_quant(x, jnp.float8_e4m3fn, _E4M3_MAX)


_q8.defvjp(lambda x: (_q8(x), None),
           lambda _, g: (_fake_quant(g, jnp.float8_e5m2, _E5M2_MAX),))


def _mm(x, w, mode):
    if mode == "float32":
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if mode == "bfloat16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    return jnp.matmul(_q8(x), _q8(w), precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w.astype(x.dtype) \
        + b.astype(x.dtype)


def layer_forward(h, p, n_heads: int, causal: bool, mode: str):
    """One block on ``h`` [B, L, H]."""
    b, l, width = h.shape
    d = width // n_heads

    def heads(x):
        return x.reshape(b, l, n_heads, d).transpose(0, 2, 1, 3)

    x = _layer_norm(h, p["ln1_w"], p["ln1_b"])
    q = heads(_mm(x, p["wq"], mode) + p["bq"].astype(h.dtype))
    k = heads(_mm(x, p["wk"], mode) + p["bk"].astype(h.dtype))
    v = heads(_mm(x, p["wv"], mode) + p["bv"].astype(h.dtype))
    scores = _mm(q, k.transpose(0, 1, 3, 2), mode) / math.sqrt(d)
    if causal:
        pos = jnp.arange(l)
        scores = jnp.where(pos[None, :] <= pos[:, None], scores,
                           jnp.finfo(jnp.float32).min.astype(scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _mm(probs, v, mode).transpose(0, 2, 1, 3).reshape(b, l, width)
    h = h + _mm(attn, p["wo"], mode) + p["bo"].astype(h.dtype)
    x = _layer_norm(h, p["ln2_w"], p["ln2_b"])
    x = jax.nn.gelu(_mm(x, p["w1"], mode) + p["b1"].astype(h.dtype),
                    approximate=False)
    return h + _mm(x, p["w2"], mode) + p["b2"].astype(h.dtype)


def hidden_states(weights, ids, n_heads: int, causal: bool, mode: str):
    """Final normalised hidden states [B, L, H] (traceable as a whole)."""
    l = ids.shape[1]
    h = _store(weights["wte"][ids] + weights["wpe"][jnp.arange(l)][None],
               mode)
    for p in weights["layers"]:
        h = layer_forward(h, p, n_heads, causal, mode)
    return _layer_norm(h, weights["lnf_w"], weights["lnf_b"])


# -- serving: logits layer by layer, so 24 float32 layers fit ---------------

@functools.partial(jax.jit, static_argnames=("mode",))
def _embed(wte, wpe, ids, mode):
    return _store(wte[ids] + wpe[jnp.arange(ids.shape[1])][None], mode)


_layer_jit = jax.jit(layer_forward, static_argnames=("n_heads", "causal",
                                                     "mode"))


ROWS = 256      # positions whose logits one call returns: a fixed shape,
                # so that no request's length compiles a program of its own


@functools.partial(jax.jit, static_argnames=("mode",))
def _head_rows(h, first, lnf_w, lnf_b, wte, mode):
    rows = jax.lax.dynamic_slice_in_dim(h[0], first, ROWS, axis=0)
    return _mm(_layer_norm(rows, lnf_w, lnf_b), wte.T, mode).astype(
        jnp.float32)


def logits_rows(weights, ids, first: int, n_heads: int, mode: str):
    """Causal-LM logits [ROWS, V] of one sequence ``ids`` [L] at positions
    ``first .. first + ROWS - 1`` (``L`` has to reach that far), computed
    one layer at a time."""
    if first + ROWS > ids.shape[0]:
        raise ValueError("sequence of %d too short for rows %d..%d"
                         % (ids.shape[0], first, first + ROWS))
    h = _embed(weights["wte"], weights["wpe"], ids[None], mode)
    for p in weights["layers"]:
        h = _layer_jit(h, p, n_heads=n_heads, causal=True, mode=mode)
    return _head_rows(h, jnp.asarray(first, jnp.int32), weights["lnf_w"],
                      weights["lnf_b"], weights["wte"], mode)


@jax.jit
def gaps_below_best(ref_logits, tokens):
    """Per row, how far the reference's logit of ``tokens`` lies below the
    reference's best, and whether it is the best."""
    best = jnp.max(ref_logits, axis=-1)
    mine = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(ref_logits, axis=-1) == tokens


# -- training: masked-LM loss, gradients in blocks of rows, AdamW -----------

def _nll_sum(weights, ids, labels, n_heads, causal, mode):
    h = hidden_states(weights, ids, n_heads, causal, mode)
    logits = _mm(h, weights["wte"].T, mode).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels != IGNORE
    safe = jnp.where(valid, labels, 0)
    picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


_nll_and_grad = jax.jit(jax.value_and_grad(_nll_sum),
                        static_argnames=("n_heads", "causal", "mode"))


def loss_and_grads(weights, blocks, count: float, n_heads: int,
                   causal: bool, mode: str):
    """Mean masked-LM loss over the whole batch and its gradient, summed
    over ``blocks`` of rows (``(ids, labels)`` pairs) so that float32
    activations fit; ``count`` is the batch's number of predicted
    positions."""
    total, grads = None, None
    for ids, labels in blocks:
        nll, g = _nll_and_grad(weights, ids, labels, n_heads=n_heads,
                               causal=causal, mode=mode)
        total = nll if total is None else total + nll
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / count, jax.tree.map(lambda g: g / count, grads)


@functools.partial(jax.jit, static_argnames=("step",), donate_argnums=(0, 2,
                                                                      3))
def _adamw(params, grads, m1, m2, step, lr, beta1, beta2, eps, decay):
    def one(p, g, a, b):
        a = beta1 * a + (1 - beta1) * g
        b = beta2 * b + (1 - beta2) * jnp.square(g)
        mhat = a / (1 - beta1 ** step)
        vhat = b / (1 - beta2 ** step)
        return (p * (1 - lr * decay) - lr * mhat / (jnp.sqrt(vhat) + eps),
                a, b)
    out = jax.tree.map(one, params, grads, m1, m2)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


_leaf_norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(
    x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])
_diff_norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
    x.astype(jnp.float32) - y.astype(jnp.float32))))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def train_steps(weights, batches, n_heads: int, causal: bool, mode: str,
                hyper: dict, make_initial):
    """Follow ``len(batches)`` AdamW steps from ``weights``; a batch is
    ``(blocks of rows, number of predicted positions)``.

    Returns ``(losses, first_grad_norms, change_norms)``: the loss of each
    step, the norm of every leaf of the first step's gradient, and the norm
    of every leaf's change after the last step.  ``make_initial()`` makes
    the starting weights again for that last subtraction, so only one copy
    lives through the steps."""
    zeros = lambda: jax.tree.map(jnp.zeros_like, weights)
    params, m1, m2 = weights, zeros(), zeros()
    losses, first = [], None
    for t, (blocks, count) in enumerate(batches, 1):
        loss, grads = loss_and_grads(params, blocks, count, n_heads, causal,
                                     mode)
        losses.append(float(loss))
        if first is None:
            first = [float(x) for x in _leaf_norms(grads)]
        params, m1, m2 = _adamw(params, grads, m1, m2, t, hyper["lr"],
                                hyper["beta1"], hyper["beta2"],
                                hyper["epsilon"], hyper["weight_decay"])
    change = [float(x) for x in _diff_norms(params, make_initial())]
    return losses, first, change
