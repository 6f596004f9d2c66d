"""Device-mesh placement for the sharded decode engine (GSPMD serving).

The training side has run dp×mp×pp over meshes since the
``distributed/meta_parallel`` stack landed; this module brings the SAME
mesh/axis-rule machinery to the serving side, so one engine serves
models bigger than one chip and batches bigger than one chip's HBM
(docs/DESIGN.md §5k).

Design, in one paragraph: the pool's batched decode step is already
row-independent (the per-slot index vector means slot ``i``'s K/V,
position and sampled token never read slot ``j``'s), so sharding the
SLOT axis over a ``dp`` mesh axis is pure placement — XLA partitions
the step into per-shard programs with no cross-shard communication on
the dp axis.  Sharding attention heads and the MLP hidden dimension
over an ``mp`` axis splits the weights and the cache's head axis the
way ``meta_parallel/mp_layers.py`` splits the training matmuls: XLA's
SPMD partitioner inserts exactly the all-reduces the hand-written
tensor-parallel layers would (the GSPMD design).
Nothing about the traced step functions changes — :class:`DecodeMesh`
only PLACES weights, cache, and per-step vectors with
``NamedSharding``/``PartitionSpec`` rules, and the compiler does the
rest.  The allocator side (per-dp-shard block partition, per-shard
scratch blocks, logical→(shard, local-slot) slot mapping) lives in
``inference.GenerationPool``.

Axis rules (the serving analog of a GSPMD model's default sharding
rules):

==========================  =======================  ==================
array                        shape                    PartitionSpec
==========================  =======================  ==================
dense cache k/v              [slots, H, max_len, D]   P('dp', 'mp')
dense cache scales           [slots, H, max_len]      P('dp', 'mp')
paged pool k/v               [blocks, H, bs, D]       P('dp', 'mp')
paged pool scales            [blocks, H, bs]          P('dp', 'mp')
block table                  [slots, max_blocks]      P('dp')
cache index                  [slots]                  P('dp')
step token / active vector   [slots]                  P('dp')
q/k/v projection weight      [d_model, H*D]           P(None, 'mp')
q/k/v projection bias        [H*D]                    P('mp')
out projection weight        [H*D, d_model]           P('mp', None)
MLP linear1 weight / bias    [d_model, ffn] / [ffn]   P(None,'mp')/P('mp')
MLP linear2 weight           [ffn, d_model]           P('mp', None)
everything else              (embeddings, norms, …)   P()  (replicated)
==========================  =======================  ==================
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..core.errors import InvalidArgumentError

__all__ = ["DecodeMesh"]


class DecodeMesh:
    """A ``dp`` × ``mp`` device mesh plus the decode-path placement
    rules: ``dp`` shards the pool's SLOT axis (and the paged block
    pool), ``mp`` shards attention heads / MLP hidden.

    ``devices=None`` takes the first ``dp * mp`` of ``jax.devices()``;
    on CPU, tests force 8 host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tier-1
    conftest does this), so dp=2 / mp=2 / dp×mp meshes are exercisable
    without an accelerator.

    ``DecodeMesh(1, 1)`` is a valid single-device mesh (the
    baseline a dp/mp mesh is compared with); ``mesh=None`` on the pool/session side is the
    fully-unsharded legacy path — the two are numerically identical but
    compile different (mesh-annotated) executables.
    """

    def __init__(self, dp: int = 1, mp: int = 1, devices=None,
                 collective_quant: str = "none",
                 collective_quant_scale: str = "block"):
        import jax
        from jax.sharding import Mesh

        from ..distributed.qcollectives import (normalize_collective_quant,
                                                normalize_collective_scale)

        # the mp-axis activation-collective mode rides the MESH (the
        # session/pool inherit it, and may override per-instance): the
        # choice is a property of the interconnect the mesh spans, not
        # of any one session.  "none" = the GSPMD fp32 all-reduce
        # exactly as today; "int8" = the explicit block-quantized
        # two-stage reduction (distributed.qcollectives, docs §5r) at
        # the decode step's row-parallel seams
        self.collective_quant = normalize_collective_quant(collective_quant)
        self.collective_quant_scale = normalize_collective_scale(
            collective_quant_scale)
        dp, mp = int(dp), int(mp)
        if dp < 1 or mp < 1:
            raise InvalidArgumentError(
                "DecodeMesh needs dp >= 1 and mp >= 1, got dp=%r mp=%r"
                % (dp, mp))
        if devices is None:
            devices = jax.devices()
        need = dp * mp
        if len(devices) < need:
            raise InvalidArgumentError(
                "DecodeMesh(dp=%d, mp=%d) needs %d devices, have %d "
                "(on CPU, set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before jax "
                "initializes)" % (dp, mp, need, len(devices)))
        self.dp = dp
        self.mp = mp
        self.mesh = Mesh(
            np.asarray(devices[:need]).reshape(dp, mp), ("dp", "mp"))

    @property
    def devices_n(self) -> int:
        """Devices the mesh spans (dp * mp)."""
        return self.dp * self.mp

    def sharding(self, *axes):
        """``NamedSharding`` for a ``PartitionSpec(*axes)`` over this
        mesh (trailing unnamed dims replicate, the P() convention)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(*axes))

    def place(self, arr, *axes):
        """``device_put`` one array, or a list of them in one transfer
        call, under ``PartitionSpec(*axes)``."""
        import jax

        return jax.device_put(arr, self.sharding(*axes))

    # -- cache placement -------------------------------------------------
    def place_cache(self, cache):
        """Place every layer's cache entry by the axis rules; None
        leaves (float caches' scales) stay None.  Returns the placed
        pytree (same namedtuple types)."""
        from .cache import entry_layout

        out = []
        for c in cache:
            # the axis rules are the ENTRY's layout's (jit.cache: K/V
            # ('dp', 'mp'), table/index ('dp'), a recurrent state field
            # ('dp', None) with its scalar window replicated)
            axes = entry_layout(c).field_axes
            upd = {}
            for field in c._fields:
                a = getattr(c, field)
                if a is None:
                    continue
                upd[field] = self.place(a, *axes(field))
            out.append(c._replace(**upd))
        return out

    # -- weight placement ------------------------------------------------
    def validate_model(self, model) -> None:
        """mp must divide the head count and the MLP hidden size —
        otherwise a head (or hidden column) would straddle two shards
        and the cache's head-axis sharding could not align with the
        projection sharding.  dp-side divisibility (slots, blocks) is
        the pool's to check; this is the model's half."""
        heads = getattr(model, "num_heads", None)
        if heads is not None and heads % self.mp != 0:
            raise InvalidArgumentError(
                "mp=%d must divide num_heads=%d: attention sharding is "
                "head-granular (each mp shard owns whole heads so the "
                "cache's head axis aligns with the q/k/v projection "
                "sharding)" % (self.mp, heads))
        planes = int(getattr(model, "cache_planes", 1))
        if planes > 1 and self.mp > 1:
            raise InvalidArgumentError(
                "mp=%d cannot shard a cache entry of %d K/V planes: the "
                "planes lie side by side on the entry's head axis (one a "
                "pass of a stack run several times), and the mp axis "
                "would split that axis into runs of planes, not into "
                "each plane's heads; serve this model with mp=1"
                % (self.mp, planes))
        windows = [layer.window for layer in model.sublayers()
                   if getattr(layer, "window", None) is not None]
        if windows and (self.mp > 1 or self.dp > 1):
            raise InvalidArgumentError(
                "mp=%d, dp=%d cannot place a model with window entries "
                "(%d layers of window=%d): a window entry's ring is blocks "
                "of the slot's own in pools of its own, laid out for one "
                "device (mp > 1 would split its heads under a table that "
                "is not the allocator's; dp > 1 would split a pool whose "
                "blocks are numbered by slot, with one scratch block); "
                "serve this model with mp=1, dp=1"
                % (self.mp, self.dp, len(windows), windows[0]))
        inter = getattr(model, "intermediate_size", None)
        if inter is not None and inter % self.mp != 0:
            raise InvalidArgumentError(
                "mp=%d must divide intermediate_size=%d: the MLP hidden "
                "axis is sharded column-wise over mp" % (self.mp, inter))

    def _weight_specs(self, model) -> Dict[int, tuple]:
        """id(param) -> partition axes, from the model's structure.

        Walks the TransformerLM shape (encoder.layers[i].self_attn /
        linear1 / linear2); anything unmatched replicates.  Structural,
        not name-matched: a model without that shape (or with mp=1)
        simply replicates everywhere, which is always correct."""
        specs: Dict[int, tuple] = {}
        if self.mp == 1:
            return specs
        layers = getattr(getattr(model, "encoder", None), "layers", None)
        if layers is None:
            return specs
        for lyr in layers:
            attn = getattr(lyr, "self_attn", None)
            if attn is not None:
                for prj in (attn.q_proj, attn.k_proj, attn.v_proj):
                    specs[id(prj.weight)] = (None, "mp")
                    if getattr(prj, "bias", None) is not None:
                        specs[id(prj.bias)] = ("mp",)
                specs[id(attn.out_proj.weight)] = ("mp", None)
            l1 = getattr(lyr, "linear1", None)
            if l1 is not None:
                specs[id(l1.weight)] = (None, "mp")
                if getattr(l1, "bias", None) is not None:
                    specs[id(l1.bias)] = ("mp",)
            l2 = getattr(lyr, "linear2", None)
            if l2 is not None:
                specs[id(l2.weight)] = ("mp", None)
        return specs

    def place_weights(self, model) -> int:
        """Place EVERY parameter and buffer of ``model`` on the mesh —
        attention/MLP axes sharded over mp per the rules, the rest
        replicated — by swapping each param's value for its
        ``device_put`` under the matching ``NamedSharding`` (the
        ``mp_layers._place`` idiom).  Placing everything (not just the
        sharded set) matters: a weight left committed to a single
        device would conflict with mesh-committed arguments inside one
        jitted call.  Returns the number of mp-SHARDED params (0 when
        mp == 1), which callers can sanity-check."""
        import jax

        self.validate_model(model)
        specs = self._weight_specs(model)
        sharded = 0
        for p in model.parameters():
            axes = specs.get(id(p), ())
            if axes:
                sharded += 1
            p._replace_value(jax.device_put(p.value, self.sharding(*axes)))
        for lyr in model.sublayers(include_self=True):
            for name, buf in getattr(lyr, "_buffers", {}).items():
                if buf is not None and hasattr(buf, "_replace_value"):
                    buf._replace_value(
                        jax.device_put(buf.value, self.sharding()))
        return sharded

    def describe(self) -> dict:
        """JSON-safe mesh description (``cache_stats()["mesh"]``)."""
        return {"dp": self.dp, "mp": self.mp, "devices": self.devices_n,
                "collective_quant": self.collective_quant,
                "collective_quant_scale": self.collective_quant_scale}

    def __repr__(self) -> str:  # pragma: no cover - debug nicety
        return "DecodeMesh(dp=%d, mp=%d)" % (self.dp, self.mp)
