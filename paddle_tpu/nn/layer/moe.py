"""The serving expert layer: a router and the stacked weights of the
experts this layer holds, dropless (``F.sparse_experts``).

``distributed/meta_parallel/moe_layer.py`` stays the TRAINING layer
(top-2, capacity-bounded, drops what overflows, an ``ep`` axis); this one
serves.  ``held`` says which experts' weights live here: all of them on
one chip.  It exists so that a share of the experts on each of several
chips needs no new layer; the exchange between chips is not written yet
(ROADMAP M2), and a layer holding a share computes only its share's part
of the sum.
"""
from __future__ import annotations

from typing import Optional, Tuple

from ...core.errors import InvalidArgumentError
from .. import functional as F
from .. import initializer as I
from .layers import Layer


class SparseExperts(Layer):
    """``num_experts`` gated-SiLU experts of width ``expert_size``,
    ``top_k`` a token, gates renormalised over the chosen, no shared
    expert, no bias.  Parameters: ``router`` ``[H, E]``, ``w_gate`` and
    ``w_up`` ``[n, H, F]``, ``w_down`` ``[n, F, H]`` for the ``n`` experts
    ``held = (first, count)`` (default: all)."""

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Optional[Tuple[int, int]] = None,
                 initializer_range: float = 0.02):
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
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(first), int(count))
        init = I.Normal(0.0, initializer_range)
        self.router = self.create_parameter(
            [hidden_size, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_up = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_down = self.create_parameter(
            [count, expert_size, hidden_size], default_initializer=init)

    def forward(self, x):
        return F.sparse_experts(x, self.router, self.w_gate, self.w_up,
                                self.w_down, top_k=self.top_k,
                                first_expert=self.held[0])

    def extra_repr(self):
        return "experts=%d, top_k=%d, held=%r" % (
            self.num_experts, self.top_k, self.held)
