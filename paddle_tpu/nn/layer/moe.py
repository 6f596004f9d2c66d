"""The serving expert layer: a router and the stacked weights of the
experts this layer holds, dropless (``F.sparse_experts``).

``distributed/meta_parallel/moe_layer.py`` stays the TRAINING layer
(top-2, capacity-bounded, drops what overflows, an ``ep`` axis); this one
serves.  ``held`` says which experts' weights live here: all of them
(``models.BlockDiffusionMoELM`` in its cell: 128 of 128) or one chip's
share of a deployment that spreads them (``models.LatentMoELM`` in its
cell: 12 of 192).  A layer holding a share computes its share's part of
the routed sum (``routed``); a SHARED expert, which every token goes
through, is computed by every holder and counted once when the shares are
summed (``forward`` adds it; the 16 holders' ``routed`` plus ``shared``
once are the layer).  The exchange between chips is not written yet
(ROADMAP M2).
"""
from __future__ import annotations

from typing import Optional, Tuple

from ...core.errors import InvalidArgumentError
from .. import functional as F
from .. import initializer as I
from .layers import Layer


class SparseExperts(Layer):
    """``num_experts`` gated-SiLU experts of width ``expert_size``,
    ``top_k`` a token, gates renormalised over the chosen, no bias.
    Parameters: ``router`` ``[H, E]``, ``w_gate`` and ``w_up`` ``[n, H,
    F]``, ``w_down`` ``[n, F, H]`` for the ``n`` experts ``held = (first,
    count)`` (default: all).  The router's rule is data: ``scoring``
    (``"softmax"`` or ``"sigmoid"``), ``n_group``/``topk_group`` (the
    group limit; 1: none) and ``routed_scale`` (``F.route_top_k``).
    ``shared_size`` > 0 adds ONE shared expert of that width (``shared``,
    an ``nn.GatedMLP``) that every token goes through."""

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Optional[Tuple[int, int]] = None,
                 initializer_range: float = 0.02,
                 scoring: str = "softmax", n_group: int = 1,
                 topk_group: int = 1, routed_scale: float = 1.0,
                 shared_size: int = 0):
        super().__init__()
        first, count = (0, num_experts) if held is None else held
        if not 1 <= top_k <= num_experts:
            raise InvalidArgumentError(
                "top_k=%d must lie in 1..num_experts=%d"
                % (top_k, num_experts))
        if first < 0 or count < 1 or first + count > num_experts:
            raise InvalidArgumentError(
                "held=(first, count)=%r is no range of the %d experts"
                % ((first, count), num_experts))
        if scoring not in ("softmax", "sigmoid"):
            raise InvalidArgumentError(
                "scoring must be 'softmax' or 'sigmoid', got %r"
                % (scoring,))
        if n_group < 1 or num_experts % n_group \
                or not 1 <= topk_group <= n_group \
                or topk_group * (num_experts // n_group) < top_k \
                or (n_group > 1 and (scoring != "sigmoid"
                                     or num_experts // n_group < 2)):
            raise InvalidArgumentError(
                "n_group=%d, topk_group=%d do not limit %d experts to "
                "groups that hold top_k=%d (the group limit is the "
                "sigmoid rule's, over groups of at least 2)"
                % (n_group, topk_group, num_experts, top_k))
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(first), int(count))
        self.scoring, self.routed_scale = scoring, float(routed_scale)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        init = I.Normal(0.0, initializer_range)
        self.router = self.create_parameter(
            [hidden_size, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_up = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_down = self.create_parameter(
            [count, expert_size, hidden_size], default_initializer=init)

        from .transformer import GatedMLP

        self.shared = GatedMLP(hidden_size, int(shared_size)) \
            if shared_size else None

    def routed(self, x):
        """The held experts' part of the routed sum."""
        return F.sparse_experts(x, self.router, self.w_gate, self.w_up,
                                self.w_down, top_k=self.top_k,
                                first_expert=self.held[0],
                                scoring=self.scoring, n_group=self.n_group,
                                topk_group=self.topk_group,
                                routed_scale=self.routed_scale)

    def forward(self, x):
        out = self.routed(x)
        return out if self.shared is None else out + self.shared(x)

    def extra_repr(self):
        return "experts=%d, top_k=%d, held=%r" % (
            self.num_experts, self.top_k, self.held)
