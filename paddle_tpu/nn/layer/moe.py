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

**The router is the layer's own matrix, or a layer.**  By default the
scores are ``x @ router`` (``router`` ``[H, E]``, a parameter of this
layer).  ``router=`` a layer that maps ``(x, r_prev) -> (scores, r)``
replaces the matrix: the router then has a STATE that travels through the
depth of the model beside the residual stream (:class:`DepthMLPRouter`),
and ``routed`` / ``forward`` take ``r_prev`` and hand ``r`` on.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.errors import InvalidArgumentError
from ...framework.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .common import Linear
from .layers import Layer
from .norm import RMSNorm


class DepthMLPRouter(Layer):
    """A router that is a small network with a residual stream of its own
    through the depth of the model (the ZAYA1 family).  On a layer's normed
    input ``m [.., H]`` and the router state ``r_prev [.., width]`` of the
    layer before::

        r = m W_dn + b_dn + gamma * r_prev        (the first layer: no term)
        z = rmsnorm(r)
        s = W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2)                  [.., E]

    ``forward(m, r_prev) -> (s, r)``: the scores go to the experts'
    choice, ``r`` to the next layer's router.  ``r``, the norm, the network
    and the scores are float32 whatever the parameters are stored in
    (``W_dn``'s product accumulates in float32; the three small products
    run at full float32 precision: a score rounded to eight bits swaps
    experts at the cut).  ``r`` and ``s`` are raw arrays, as a cache is."""

    def __init__(self, hidden_size: int, width: int, num_experts: int,
                 first: bool = False, norm_epsilon: float = 1e-6):
        super().__init__()
        self.width, self.first = int(width), bool(first)
        self.down = Linear(hidden_size, width)
        if not first:
            self.gamma = self.create_parameter(
                [width], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(width, norm_epsilon)
        self.fc1 = Linear(width, width)
        self.fc2 = Linear(width, width)
        self.out = Linear(width, num_experts, bias_attr=False)

    def forward(self, m, r_prev=None):
        f32 = jnp.float32
        if self.first and r_prev is not None:
            raise InvalidArgumentError(
                "the router of the first layer takes no state of a layer "
                "before")
        if not self.first and r_prev is None:
            raise InvalidArgumentError(
                "the router of a later layer takes the state of the layer "
                "before")

        def affine(x, layer):
            y = jnp.matmul(x, layer.weight.value.astype(f32),
                           precision=jax.lax.Precision.HIGHEST)
            return y if layer.bias is None else y + layer.bias.value.astype(f32)

        r = jnp.matmul(getattr(m, "value", m), self.down.weight.value,
                       preferred_element_type=f32) \
            + self.down.bias.value.astype(f32)
        if not self.first:
            r = r + self.gamma.value.astype(f32) * r_prev
        z = F.rms_norm(r, self.norm.weight.value.astype(f32),
                       self.norm._epsilon)
        h = jax.nn.gelu(affine(z, self.fc1), approximate=False)
        h = jax.nn.gelu(affine(h, self.fc2), approximate=False)
        return affine(h, self.out), r


class SparseExperts(Layer):
    """``num_experts`` gated experts of width ``expert_size``, ``top_k`` a
    token, no bias; the gate is ``activation`` (``"silu"``, or ``"relu"``:
    ``F.EXPERT_ACTIVATIONS``).
    Parameters: ``router`` ``[H, E]`` (or the layer given as ``router=``:
    the module docstring), ``w_gate`` and ``w_up`` ``[n, H,
    F]``, ``w_down`` ``[n, F, H]`` for the ``n`` experts ``held = (first,
    count)`` (default: all).  The router's rule is data: ``scoring``
    (``"softmax"`` or ``"sigmoid"``), ``n_group``/``topk_group`` (the
    group limit; 1: none), ``routed_scale`` and ``renormalise`` (the
    chosen gates made to sum to 1, or left as they stand:
    ``F.route_top_k``).
    ``shared_size`` > 0 adds ONE shared expert of that width (``shared``,
    an ``nn.GatedMLP``) that every token goes through."""

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Optional[Tuple[int, int]] = None,
                 initializer_range: float = 0.02,
                 scoring: str = "softmax", n_group: int = 1,
                 topk_group: int = 1, routed_scale: float = 1.0,
                 shared_size: int = 0, renormalise: bool = True,
                 router: Optional[Layer] = None,
                 activation: str = "silu"):
        super().__init__()
        if activation not in F.EXPERT_ACTIVATIONS:
            raise InvalidArgumentError(
                "activation must be one of %s, got %r"
                % (sorted(F.EXPERT_ACTIVATIONS), activation))
        self.activation = activation
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
        self.renormalise = bool(renormalise)
        init = I.Normal(0.0, initializer_range)
        self.router = router if router is not None else \
            self.create_parameter([hidden_size, num_experts],
                                  default_initializer=init)
        self.w_gate = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_up = self.create_parameter(
            [count, hidden_size, expert_size], default_initializer=init)
        self.w_down = self.create_parameter(
            [count, expert_size, hidden_size], default_initializer=init)

        from .transformer import GatedMLP

        self.shared = GatedMLP(hidden_size, int(shared_size)) \
            if shared_size else None

    def route_at(self, rows: int) -> str:
        """The route ``F.sparse_experts`` takes when this layer is given
        ``rows`` rows (``F.expert_route``: from shapes alone)."""
        _, width, size = self.w_gate.shape
        return F.expert_route(rows, self.held[1], self.num_experts,
                              self.top_k, width, size,
                              self.w_gate.value.dtype.itemsize)

    def scores_of(self, x):
        """The router matrix's logits of every expert for the rows of
        ``x`` ``[.., H]``, ``[rows, E]`` float32: what ``routed`` computes
        from its own input, for a caller whose router reads ANOTHER tensor
        than the experts do (``routed(x, scores=)``)."""
        if isinstance(self.router, Layer):
            raise InvalidArgumentError(
                "scores_of is the router MATRIX's product; this layer's "
                "router is a layer with a state of its own")
        with jax.named_scope("router"):
            return jnp.matmul(x.value.reshape(-1, x.shape[-1]),
                              self.router.value,
                              preferred_element_type=jnp.float32)

    def routed(self, x, r_prev=None, scores=None):
        """The held experts' part of the routed sum; under a router layer
        ``(part, r)``, ``r`` the router's state for the next layer.
        ``scores`` ``[rows, E]`` float32: the router's logits computed by
        the caller from another tensor than ``x`` (``scores_of``); the
        layer's own router is then not run."""
        carried = isinstance(self.router, Layer)
        xt = x.value.reshape(-1, x.shape[-1])
        if scores is not None:
            if carried or r_prev is not None:
                raise InvalidArgumentError(
                    "scores= replaces the router matrix's product; a "
                    "router layer computes its own from its state")
        elif carried:
            scores, r = self.router(x, r_prev)
        else:
            with jax.named_scope("router"):
                scores = jnp.matmul(xt, self.router.value,
                                    preferred_element_type=jnp.float32)
        out = F.sparse_experts(xt, scores, self.w_gate.value,
                               self.w_up.value, self.w_down.value,
                               top_k=self.top_k, first_expert=self.held[0],
                               scoring=self.scoring, n_group=self.n_group,
                               topk_group=self.topk_group,
                               routed_scale=self.routed_scale,
                               renormalise=self.renormalise,
                               activation=self.activation)
        out = Tensor(out.reshape(x.shape), stop_gradient=True)
        return (out, r) if carried else out

    def forward(self, x, r_prev=None, scores=None):
        """The layer's output; under a router layer ``(output, r)``."""
        out = self.routed(x, r_prev, scores)
        if self.shared is None:
            return out
        if isinstance(out, tuple):
            return out[0] + self.shared(x), out[1]
        return out + self.shared(x)

    def extra_repr(self):
        return "experts=%d, top_k=%d, held=%r" % (
            self.num_experts, self.top_k, self.held)
