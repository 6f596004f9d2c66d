"""Loss functionals (reference: python/paddle/nn/functional/loss.py; fused
kernel parity: softmax_with_cross_entropy_op.cc:325).

The hard-label softmax cross-entropy is ONE op with its own backward
(``_softmax_xent_rows``, a ``jax.custom_vjp``), as the reference's fused
CUDA kernel is.  It reads the logits in the type they are stored in,
up-casts them to float32 inside its reductions (never as an array), keeps
``logsumexp`` a row and the logits themselves for the backward, and returns
the gradient in the logits' type.  The log-softmax + gather
composition it replaces was six device operations on the chip, one of
them a float32 copy of the logits (PERF.md section 6).  Soft labels,
``use_softmax=False`` and label smoothing keep that composition.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.errors import InvalidArgumentError


def _reduce(loss, reduction: str):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    if reduction == "none":
        return loss
    raise InvalidArgumentError("reduction must be mean|sum|none, got %r" % reduction)


def log_loss(input, label, epsilon: float = 1e-4):
    return -label * jnp.log(input + epsilon) - (1 - label) * jnp.log(1 - input + epsilon)


def _label_hits(x, lbl):
    """``[..., V]`` mask of each row's label: a select inside a reduction
    stands where a gather (and, backward, a scatter) would."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) \
        == lbl[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_xent_rows(x, lbl, ignore_index):
    """Per-row ``logsumexp(x) - x[lbl]`` over the last axis, 0 where
    ``lbl == ignore_index``: ``x`` ``[..., V]`` of any float type, ``lbl``
    ``[...]`` int32.  The loss is float32 (float64 from float64 logits)."""
    return _xent_rows_fwd(x, lbl, ignore_index)[0]


def _xent_rows_fwd(x, lbl, ignore_index):
    with jax.named_scope("cross_entropy"):
        xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
        m = jnp.max(xf, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(xf - m[..., None]), axis=-1))
        picked = jnp.sum(jnp.where(_label_hits(x, lbl), xf, 0), axis=-1)
        loss = jnp.where(lbl != ignore_index, lse - picked, 0)
    return loss, (x, lse, lbl)


def _xent_rows_bwd(ignore_index, res, g):
    x, lse, lbl = res
    with jax.named_scope("cross_entropy"):
        p = jnp.exp(x.astype(lse.dtype) - lse[..., None])
        dx = (p - _label_hits(x, lbl).astype(lse.dtype)) \
            * g.astype(lse.dtype)[..., None]
        dx = jnp.where((lbl != ignore_index)[..., None], dx, 0)
    return dx.astype(x.dtype), None


_softmax_xent_rows.defvjp(_xent_rows_fwd, _xent_rows_bwd)


def _is_soft(input, label, soft_label) -> bool:
    return bool(soft_label) or (label.ndim == input.ndim
                                and label.shape == input.shape)


def _takes_fused_core(input, label, weight=None, ignore_index=-100,
                      reduction="mean", soft_label=False, axis=-1,
                      use_softmax=True, label_smoothing=0.0) -> bool:
    """Whether ``cross_entropy`` with these arguments runs the fused core,
    which up-casts inside itself (``framework/dispatch.py`` asks, so that
    autocast's black list puts no float32 copy in front of it)."""
    return bool(use_softmax) and not label_smoothing > 0.0 \
        and not _is_soft(input, label, soft_label)


def cross_entropy(
    input,
    label,
    weight=None,
    ignore_index: int = -100,
    reduction: str = "mean",
    soft_label: bool = False,
    axis: int = -1,
    use_softmax: bool = True,
    label_smoothing: float = 0.0,
):
    """softmax_with_cross_entropy fused semantics.

    ``input``: logits (or probabilities when use_softmax=False); ``label``:
    int class ids (or soft distributions when soft_label=True).  With class
    ids, ``use_softmax`` and no smoothing the loss is computed in float32
    whatever the logits' type, and is float32.
    """
    if _takes_fused_core(input, label, soft_label=soft_label,
                         use_softmax=use_softmax,
                         label_smoothing=label_smoothing):
        return _hard_label_loss(input, label, weight, ignore_index,
                                reduction, axis)
    if use_softmax:
        logp = jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.clip(input, 1e-10, 1.0))
    if _is_soft(input, label, soft_label):
        soft = label
        if label_smoothing > 0.0:
            n = input.shape[axis]
            soft = soft * (1.0 - label_smoothing) + label_smoothing / n
        loss = -jnp.sum(soft * logp, axis=axis)
        return _reduce(loss, reduction)
    lbl, valid, safe = _class_ids(input, label, ignore_index, axis)
    picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis), axis=axis)
    loss = -jnp.squeeze(picked, axis=axis)
    if label_smoothing > 0.0:
        smooth_loss = -jnp.mean(logp, axis=axis)
        loss = (1.0 - label_smoothing) * loss + label_smoothing * smooth_loss
    return _weigh_and_reduce(loss, valid, safe, weight, reduction)


def _class_ids(input, label, ignore_index, axis):
    lbl = label
    if lbl.ndim == input.ndim and lbl.shape[axis] == 1:
        lbl = jnp.squeeze(lbl, axis=axis)
    lbl = lbl.astype(jnp.int32)
    valid = lbl != ignore_index
    return lbl, valid, jnp.where(valid, lbl, 0)


def _weigh_and_reduce(loss, valid, safe, weight, reduction):
    """Class weights, zeros on ignored rows and the reduction of a per-row
    hard-label loss; ``mean`` is over the valid rows (their weights)."""
    if weight is not None:
        w = weight[safe]
        loss = loss * w
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        if weight is not None:
            # no valid row: 0 / 1 and not 0 / 0, as without weights
            denom = jnp.sum(jnp.where(valid, w, 0.0))
            denom = jnp.where(denom == 0, 1.0, denom)
        else:
            denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
        return jnp.sum(loss) / denom
    return _reduce(loss, reduction)


def _hard_label_loss(input, label, weight, ignore_index, reduction, axis):
    lbl, valid, safe = _class_ids(input, label, ignore_index, axis)
    loss = _softmax_xent_rows(jnp.moveaxis(input, axis, -1), lbl,
                              ignore_index)
    if weight is not None:
        weight = weight.astype(loss.dtype)
    return _weigh_and_reduce(loss, valid, safe, weight, reduction)


cross_entropy.amp_upcasts_inside = _takes_fused_core


def softmax_with_cross_entropy(
    logits, label, soft_label: bool = False, ignore_index: int = -100,
    numeric_stable_mode: bool = True, return_softmax: bool = False, axis: int = -1
):
    loss = cross_entropy(
        logits, label, soft_label=soft_label, ignore_index=ignore_index,
        reduction="none", axis=axis,
    )
    loss = jnp.expand_dims(loss, axis)
    if return_softmax:
        return loss, jax.nn.softmax(logits, axis=axis)
    return loss


def _swce_takes_fused_core(logits, label, soft_label=False,
                           ignore_index=-100, numeric_stable_mode=True,
                           return_softmax=False, axis=-1) -> bool:
    # the softmax handed back is computed in the type it is given
    return not return_softmax and _takes_fused_core(
        logits, label, soft_label=soft_label)


softmax_with_cross_entropy.amp_upcasts_inside = _swce_takes_fused_core


def nll_loss(input, label, weight=None, ignore_index: int = -100, reduction: str = "mean"):
    # input: log-probabilities [N, C, ...]
    lbl = label.astype(jnp.int32)
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0)
    picked = jnp.take_along_axis(input, jnp.expand_dims(safe, 1), axis=1)
    loss = -jnp.squeeze(picked, axis=1)
    w = None
    if weight is not None:
        w = weight[safe]
        loss = loss * w
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        denom = jnp.sum(w * valid if w is not None else valid.astype(loss.dtype))
        return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
    return _reduce(loss, reduction)


def mse_loss(input, label, reduction: str = "mean"):
    return _reduce(jnp.square(input - label), reduction)


def l1_loss(input, label, reduction: str = "mean"):
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction: str = "mean", delta: float = 1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    return _reduce(loss, reduction)


def bce_loss(input, label, weight=None, reduction: str = "mean"):
    eps = 1e-12
    loss = -(label * jnp.log(input + eps) + (1 - label) * jnp.log(1 - input + eps))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction: str = "mean"):
    return bce_loss(input, label, weight, reduction)


def binary_cross_entropy_with_logits(
    input, label, weight=None, reduction: str = "mean", pos_weight=None
):
    if pos_weight is None:
        # numerically stable: max(x,0) - x*z + log(1 + exp(-|x|))
        loss = jnp.maximum(input, 0) - input * label + jnp.log1p(jnp.exp(-jnp.abs(input)))
    else:
        loss = -(pos_weight * label * jax.nn.log_sigmoid(input)
                 + (1 - label) * jax.nn.log_sigmoid(-input))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def kl_div(input, label, reduction: str = "mean"):
    # input: log-probs; label: probs (paddle semantics)
    loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin: float = 0.0, reduction: str = "mean"):
    loss = jnp.maximum(0.0, -label * (input - other) + margin)
    return _reduce(loss, reduction)


def hinge_embedding_loss(input, label, margin: float = 1.0, reduction: str = "mean"):
    loss = jnp.where(label == 1.0, input, jnp.maximum(0.0, margin - input))
    return _reduce(loss, reduction)


def cosine_similarity(x1, x2, axis: int = 1, eps: float = 1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(jnp.square(x1), axis=axis))
    n2 = jnp.sqrt(jnp.sum(jnp.square(x2), axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha: float = 0.25, gamma: float = 2.0, reduction: str = "sum"):
    p = jax.nn.sigmoid(logit)
    ce = binary_cross_entropy_with_logits(logit, label, reduction="none")
    p_t = p * label + (1 - p) * (1 - label)
    a_t = alpha * label + (1 - alpha) * (1 - label)
    loss = a_t * jnp.power(1 - p_t, gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


def square_error_cost(input, label):
    return jnp.square(input - label)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank: int = 0,
             reduction: str = "mean", norm_by_times: bool = False):
    """CTC loss (reference: F.ctc_loss over the warpctc op).

    log_probs: [T, N, C] unnormalized logits (softmax applied internally,
    warpctc semantics); labels: [N, L] padded; lengths: [N].  The standard
    alpha recursion over the blank-extended label runs as one ``lax.scan``
    over time — static shapes, per-sample lengths handled by masking.
    """
    from jax import lax

    lp = jax.nn.log_softmax(jnp.asarray(log_probs, jnp.float32), axis=-1)
    T, N, C = lp.shape
    labels = jnp.asarray(labels, jnp.int32)
    L = labels.shape[1]
    S = 2 * L + 1
    input_lengths = jnp.asarray(input_lengths, jnp.int32)
    label_lengths = jnp.asarray(label_lengths, jnp.int32)

    # blank-extended target: [blank, l1, blank, l2, ..., blank]
    ext = jnp.full((N, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    neg_inf = jnp.float32(-1e30)

    # skip transition s-2 -> s allowed when ext[s] != blank and != ext[s-2]
    can_skip = jnp.zeros((N, S), bool)
    can_skip = can_skip.at[:, 2:].set(
        (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2]))
    valid_s = jnp.arange(S)[None, :] <= 2 * label_lengths[:, None]

    def emit(t):
        return jnp.take_along_axis(lp[t], ext, axis=1)  # [N, S]

    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(label_lengths > 0,
                  jnp.take_along_axis(lp[0], ext[:, 1:2], axis=1)[:, 0],
                  neg_inf))

    def final_of(alpha):
        lastb = jnp.take_along_axis(alpha, (2 * label_lengths)[:, None],
                                    axis=1)[:, 0]
        lastl = jnp.take_along_axis(
            alpha, jnp.maximum(2 * label_lengths - 1, 0)[:, None],
            axis=1)[:, 0]
        lastl = jnp.where(label_lengths > 0, lastl, neg_inf)
        return jnp.logaddexp(lastb, lastl)

    def step(carry, t):
        alpha, final = carry
        stay = alpha
        prev1 = jnp.concatenate(
            [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate(
            [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(can_skip, prev2, neg_inf)
        new = jnp.logaddexp(jnp.logaddexp(stay, prev1), prev2) + emit(t)
        new = jnp.where(valid_s, new, neg_inf)
        alive = (t < input_lengths)[:, None]
        new = jnp.where(alive, new, alpha)
        # freeze each sample's final log-prob at its last valid frame
        final = jnp.where(t == input_lengths - 1, final_of(new), final)
        return (new, final), None

    final0 = jnp.where(input_lengths == 1, final_of(alpha0),
                       jnp.full((N,), neg_inf))
    (alphaT, final), _ = lax.scan(step, (alpha0, final0),
                                  jnp.arange(1, T))
    loss = -final
    if norm_by_times:
        loss = loss / jnp.maximum(input_lengths.astype(jnp.float32), 1.0)
    if reduction == "mean":
        # warpctc mean: per-sample loss normalized by label length first
        return jnp.mean(
            loss / jnp.maximum(label_lengths.astype(jnp.float32), 1.0))
    return _reduce(loss, reduction)


def dice_loss(input, label, epsilon: float = 1e-5):
    """fluid/layers dice_loss parity: 1 - 2|X∩Y| / (|X|+|Y|)."""
    input = jnp.asarray(input)
    label = jnp.asarray(label)
    num_classes = input.shape[-1]
    if label.shape[-1] == 1:
        label = label[..., 0]
    one_hot = jax.nn.one_hot(label.astype(jnp.int32), num_classes,
                             dtype=input.dtype)
    reduce_dims = tuple(range(1, input.ndim))
    inter = 2.0 * jnp.sum(input * one_hot, axis=reduce_dims)
    union = jnp.sum(input, axis=reduce_dims) + jnp.sum(one_hot,
                                                       axis=reduce_dims)
    return jnp.mean(1.0 - (inter + epsilon) / (union + epsilon))


def npair_loss(anchor, positive, labels, l2_reg: float = 0.002):
    """fluid/layers npair_loss parity: softmax CE over anchor·positiveᵀ
    with same-label targets + L2 on the embeddings."""
    anchor = jnp.asarray(anchor)
    positive = jnp.asarray(positive)
    labels = jnp.asarray(labels).reshape(-1)
    same = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    targets = same / jnp.maximum(same.sum(axis=1, keepdims=True), 1e-9)
    sim = anchor @ positive.T
    # per-row soft-label CE, then the reference's column-weighted mean
    # (loss.py:1723-1728: reduce_sum(labels * ce, 0) then reduce_mean)
    ce = -jnp.sum(targets * jax.nn.log_softmax(sim, axis=1), axis=1)  # [N]
    celoss = jnp.mean(jnp.sum(targets * ce[:, None], axis=0))
    l2 = (jnp.mean(jnp.sum(jnp.square(anchor), 1))
          + jnp.mean(jnp.sum(jnp.square(positive), 1))) * 0.25 * l2_reg
    return celoss + l2


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse: bool = False):
    """Hierarchical sigmoid (hierarchical_sigmoid_op / matrix_bit_code.h
    SimpleCode semantics): complete-binary-tree paths by default, custom
    trees via per-sample path_table/path_code.

    input [N, D]; label [N] (or [N,1]); weight [num_classes-1, D] (or
    [num_nodes, D] for custom trees); returns [N, 1] losses.
    """
    input = jnp.asarray(input)
    label = jnp.asarray(label).reshape(-1).astype(jnp.int32)
    weight = jnp.asarray(weight)
    N = input.shape[0]

    if path_table is not None:
        pt_ = jnp.asarray(path_table, jnp.int32)
        pc = jnp.asarray(path_code, jnp.float32)
        valid = (pt_ >= 0).astype(jnp.float32)
        idx = jnp.maximum(pt_, 0)
    else:
        # SimpleCode: c = label + num_classes; node = (c >> (bit+1)) - 1;
        # branch bit = (c >> bit) & 1; path length = floor(log2(c))
        c = label + int(num_classes)
        max_len = max(int(num_classes - 1).bit_length(), 1)
        bits = jnp.arange(max_len)
        length = jnp.floor(
            jnp.log2(c.astype(jnp.float32))).astype(jnp.int32)
        valid = (bits[None, :] < length[:, None]).astype(jnp.float32)
        idx = jnp.clip((c[:, None] >> (bits[None, :] + 1)) - 1, 0,
                       weight.shape[0] - 1)
        pc = ((c[:, None] >> bits[None, :]) & 1).astype(jnp.float32)

    w = weight[idx]                       # [N, L, D]
    pre = jnp.einsum("nld,nd->nl", w, input)
    if bias is not None:
        pre = pre + jnp.asarray(bias).reshape(-1)[idx]
    # BCE-with-logits against the branch bits, masked to real path length
    per_bit = jnp.maximum(pre, 0) - pre * pc + jnp.log1p(
        jnp.exp(-jnp.abs(pre)))
    return (per_bit * valid).sum(axis=1, keepdims=True)
