"""KV-cached autoregressive decode engine: the jitted prefill/decode split.

The serving-side analog of ``TrainStep``: where training compiles ONE
fused step, generation compiles exactly TWO functions —

- ``prefill(ids) -> (cache, first_token)``: one causal forward over the
  (bucket-padded) prompt that also writes every position's K/V into a
  preallocated ``[B, H, max_len, D]`` cache
  (``MultiHeadAttention.DecodeCache``).  Prompt lengths are rounded up to
  a BUCKET so a handful of compilations covers every request length; the
  cache index is set to the TRUE length, so pad garbage is never
  attended.
- ``decode(cache, token) -> (cache, next_token)``: a single-token step
  whose shapes are IDENTICAL every call — the cache is written in place
  via ``lax.dynamic_update_slice`` and (off-CPU) DONATED to XLA, so the
  per-token cost is one fused dispatch over O(max_len) cache reads
  instead of a full O(L²) re-forward, with no per-step compilation and no
  host round-trip beyond the sampled token ids.

Sampling (greedy / temperature / top-k / top-p) runs INSIDE the compiled
step with its config as per-row DATA (``SamplingState``: traced ``[B]``
vectors for temperature/top-k/top-p/seed plus the per-row draw counter),
so a 128-token generation is 1 prefill dispatch + 127 decode dispatches
and a batch may mix greedy and arbitrarily-sampled rows — changing a
request's sampling config never retraces anything.  Row r's stream is
``fold_in(PRNGKey(seed[r]), step[r])``: a pure function of the request's
own (seed, draw index), independent of slot position or batch
composition, which is what makes preempted/migrated sampled requests
resume byte-identically.

Reference parity: the reference serves generation through external
inference engines; here the engine is native because the jaxpr is the
program.  The portable-O(1)-cache design follows the compiler-first
discipline in PAPERS.md ("Portable O(1) Autoregressive Caching for
Inference"): shape-static cache updates the compiler can fuse, not a
runtime-managed allocator.
"""
from __future__ import annotations

import contextlib
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import InvalidArgumentError
from ..core.random import next_key
from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from . import aot

__all__ = ["DecodeSession", "sample_logits", "sample_logits_data",
           "SamplingState", "make_sampling_state", "check_sampling",
           "default_buckets", "FINISH_EOS", "FINISH_LENGTH",
           "classify_finish", "truncate_at_eos"]

# The decode layer's finish-reason vocabulary: a generation ends either
# because the model emitted the EOS id or because the max_new_tokens
# budget ran out.  The serving layer (paddle_tpu.serving) layers its
# scheduler-side reasons (deadline expiry, caller cancellation) on top;
# they can never originate here, because the compiled step knows nothing
# about wall clocks or callers.
FINISH_EOS = "eos"
FINISH_LENGTH = "length"


def classify_finish(tokens, eos_id) -> str:
    """Finish reason for ONE finished row's generated tokens:
    ``FINISH_EOS`` if the row terminated on ``eos_id``, else
    ``FINISH_LENGTH``.  A row that spends its whole budget *and* lands
    on EOS with its last token counts as EOS — the model stopped, the
    budget coincidentally agreeing."""
    toks = np.asarray(tokens)
    if eos_id is not None and toks.size and int(toks[-1]) == int(eos_id):
        return FINISH_EOS
    return FINISH_LENGTH


def truncate_at_eos(tokens, eos_id):
    """Truncate a 1-D emitted-token array at the FIRST ``eos_id``
    (inclusive); with no EOS present (or ``eos_id=None``) the tokens
    pass through unchanged.

    This is the speculative COMMIT rule: a verify step may accept a
    whole chunk of draft tokens at once, and an EOS anywhere inside the
    accepted prefix ends the request THERE — the accepted tail after
    the EOS (and the bonus token) must never be emitted, exactly as the
    one-token-at-a-time decode loop would have stopped.  The truncated
    array always ends on the EOS, so ``classify_finish`` sees
    ``FINISH_EOS`` for it."""
    toks = np.asarray(tokens)
    if eos_id is None or toks.size == 0:
        return toks
    hits = np.nonzero(toks == int(eos_id))[0]
    if hits.size:
        return toks[:int(hits[0]) + 1]
    return toks


def sample_logits(logits, key, temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0):
    """Sample token ids [B] from logits [B, V] (trace-friendly).

    ``temperature == 0`` is greedy argmax (deterministic, key unused);
    otherwise temperature scaling, then optional top-k truncation, then
    optional nucleus (top-p) truncation, then a categorical draw.  The
    sampling config is PYTHON-static: each distinct config is part of the
    compiled step, never a runtime branch.
    """
    if temperature < 0.0:
        raise InvalidArgumentError(
            "temperature must be >= 0 (0 = greedy), got %r" % temperature)
    if not 0.0 < top_p <= 1.0:
        # top_p == 0 would mask EVERY token (exclusive prefix mass 0 >= 0)
        # and silently degrade to uniform sampling over the vocab
        raise InvalidArgumentError(
            "top_p must be in (0, 1], got %r" % top_p)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, logits.dtype)
    logits = logits / jnp.asarray(temperature, logits.dtype)
    if top_k and top_k > 0 and top_k < logits.shape[-1]:
        # partial selection, not a full O(V log V) sort: this runs inside
        # the per-token compiled decode step over the whole vocab
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc.astype(jnp.float32), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # drop tokens whose EXCLUSIVE prefix mass already reaches top_p
        # (the smallest set covering top_p is kept; ties keep both)
        cut = (cum - probs) >= top_p
        kept_min = jnp.min(jnp.where(cut, jnp.inf,
                                     sorted_desc.astype(jnp.float32)),
                           axis=-1, keepdims=True)
        logits = jnp.where(logits.astype(jnp.float32) < kept_min, neg,
                           logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class SamplingState(NamedTuple):
    """Per-row decode-time request state, as DATA (docs/DESIGN.md §5q).

    Every field is a traced ``[B]`` vector riding the compiled step as an
    ordinary argument — NEVER a Python constant baked into the trace —
    so one executable serves any mix of greedy and sampled rows and any
    mix of LoRA adapters with zero retraces:

    - ``temperature`` f32 (0 = greedy argmax for that row),
    - ``top_k`` i32 (<= 0 or >= vocab keeps the whole vocab),
    - ``top_p`` f32 (1 keeps everything),
    - ``seed``/``step`` u32: row r draws with
      ``fold_in(PRNGKey(seed[r]), step[r])`` where ``step`` counts the
      row's own draws — the stream is a pure function of the REQUEST's
      (seed, draw index), independent of slot position or batch
      composition, so preemption/migration resumes byte-identically,
    - ``adapter`` i32: the row's LoRA adapter id (``nn.lora``; 0 is the
      reserved identity row — the base model).
    """

    temperature: jax.Array
    top_k: jax.Array
    top_p: jax.Array
    seed: jax.Array
    step: jax.Array
    adapter: jax.Array


def check_sampling(temperature, top_p) -> None:
    """Typed admission-edge validation shared by the session constructor
    and the pool/engine per-request ``submit`` params (same message, so
    a bad config fails identically whichever edge it enters through)."""
    if float(temperature) < 0.0 or not 0.0 < float(top_p) <= 1.0:
        raise InvalidArgumentError(
            "sampling config: temperature must be >= 0 and top_p in "
            "(0, 1]; got temperature=%r top_p=%r" % (temperature, top_p))


def make_sampling_state(batch: int, temperature=0.0, top_k=0, top_p=1.0,
                        seed=None, step=0, adapter=0) -> SamplingState:
    """Host-side constructor of a ``[batch]`` :class:`SamplingState`.

    Scalar args broadcast to every row; array args pass through
    unchanged.  A scalar ``seed`` gives row r the stream ``seed + r``
    (distinct per row, reproducible across runs); ``seed=None`` draws a
    fresh base seed from the global key chain."""
    def vec(x, dtype):
        a = np.asarray(x, dtype)
        return jnp.asarray(np.broadcast_to(a, (batch,)) if a.ndim == 0
                           else a)

    if seed is None:
        seed = int(jax.random.randint(next_key(), (), 0,
                                      np.int32(2 ** 31 - 1)))
    s = np.asarray(seed, np.uint32)
    if s.ndim == 0:
        s = s + np.arange(batch, dtype=np.uint32)
    return SamplingState(vec(temperature, np.float32),
                         vec(top_k, np.int32), vec(top_p, np.float32),
                         jnp.asarray(s), vec(step, np.uint32),
                         vec(adapter, np.int32))


def sample_logits_data(logits, temperature, top_k, top_p, seed, step):
    """Sample token ids [B] from logits [B, V] with the config as per-row
    traced DATA (the vectors of :class:`SamplingState`) — the as-data
    twin of :func:`sample_logits`: ONE trace with one data-dependent
    ``lax.cond``, so every row of one compiled step can carry a
    different config and a step in which no row draws pays for no draw.

    Row semantics match the scalar sampler: ``temperature == 0`` is
    greedy argmax (seed unused); otherwise temperature scaling, top-k
    truncation (``top_k <= 0`` or ``>= V`` keeps all; ties at the k-th
    value keep both), then nucleus truncation (tokens whose EXCLUSIVE
    prefix mass under the sorted distribution already reaches ``top_p``
    are dropped; ``top_p == 1`` keeps all), then a categorical draw
    under ``fold_in(PRNGKey(seed[r]), step[r])``.  ONE descending sort
    serves both truncations — the masks are arithmetic over it, never a
    Python branch, so the trace is config-independent.

    The argmax over the logits as given is taken outside any branch;
    the sort, the softmax and the draw over ``[B, V]`` sit behind
    ``lax.cond(any(temperature > 0))``, a predicate over the vector the
    step is given (rows a step does not take are uploaded greedy).  A
    step with a drawing row runs the whole draw for every row and its
    greedy rows keep their argmax: the ids are the same either way.
    Under ``vmap`` a ``cond`` becomes a select that runs both branches:
    batch the rows through ``logits``, never by vmapping this."""
    temp = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        v = logits.shape[-1]
        lf = logits.astype(jnp.float32)
        tk = jnp.asarray(top_k, jnp.int32)
        tp = jnp.asarray(top_p, jnp.float32)
        neg = jnp.float32(jnp.finfo(jnp.float32).min)
        # temperature 0 rows scale by 1 (their draw is discarded for
        # argmax)
        safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
        scaled = lf / safe_t[:, None]
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        # top-k: the row's k-th largest value is the keep threshold
        kk = jnp.clip(tk, 1, v)
        kth = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
        apply_k = ((tk > 0) & (tk < v))[:, None]
        keep = jnp.where(apply_k, scaled >= kth, True)
        # top-p: smallest set covering top_p mass (exclusive-prefix
        # cut); rows with top_p == 1 never cut, so kept_min is the row
        # minimum
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cut = (cum - probs) >= tp[:, None]
        kept_min = jnp.min(jnp.where(cut, jnp.inf, sorted_desc), axis=-1,
                           keepdims=True)
        keep = keep & (scaled >= kept_min)
        masked = jnp.where(keep, scaled, neg)
        keys = jax.vmap(
            lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t))(
                jnp.asarray(seed, jnp.uint32),
                jnp.asarray(step, jnp.uint32))
        drawn = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(temp == 0, greedy, drawn).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temp > 0), draw, lambda: greedy)


def default_buckets(max_len: int, lo: int = 64) -> List[int]:
    """Power-of-two prefill buckets up to ``max_len`` (inclusive cap):
    64, 128, ... — a handful of prefill compilations covers every prompt
    length, the classic static-shape bucketing compromise."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


class DecodeSession:
    """Batched autoregressive generation with exactly two compiled
    functions (one prefill bucket + one decode step).

    All rows of a ``generate`` batch share one prompt length (the aligned
    layout whose cache index is a scalar); mixed-length concurrent
    serving is ``paddle_tpu.inference.GenerationPool``'s slot-batched
    layout on top of this class.

    ``donate=None`` donates the cache to the decode step on accelerator
    backends (XLA then updates it in place in HBM) and skips donation on
    CPU, where PjRt does not alias and would warn every compile.
    """

    def __init__(self, model: Layer, max_len: int,
                 buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, cache_dtype="float32",
                 donate: Optional[bool] = None,
                 cache_layout: str = "dense", block_size: int = 32,
                 mesh=None, route: str = "auto",
                 collective_quant: Optional[str] = None,
                 collective_quant_scale: Optional[str] = None):
        from . import _StateBinding
        from ..ops.flash_attention import normalize_decode_route

        # decode-attention routing (docs/DESIGN.md §5l): "auto" keeps
        # the measured-crossover discipline (the fused pallas kernel
        # engages only where the ops-layer gates say it wins);
        # "composition"/"pallas" force a path for tests and sweeps.
        # PYTHON-static: the route picks which ops the session's
        # executables trace, so the exactly-two-compiles contract and
        # the executable cache keys are untouched.
        self.route = normalize_decode_route(route)

        if mesh is not None:
            # GSPMD serving (docs/DESIGN.md §5k): place every weight on
            # the mesh by the decode axis rules — attention heads / MLP
            # hidden sharded over 'mp', the rest replicated — BEFORE
            # the binding snapshots parameter identities.  The traced
            # bodies are untouched; XLA partitions them from the
            # operand shardings (the pool shards the cache/slot axis
            # over 'dp' on its side)
            from .mesh import DecodeMesh

            if not isinstance(mesh, DecodeMesh):
                raise InvalidArgumentError(
                    "mesh must be a jit.mesh.DecodeMesh (or None for "
                    "single-device decode), got %r"
                    % (type(mesh).__name__,))
            mesh.place_weights(model)
        self.mesh = mesh
        # mp-axis activation-collective mode (docs §5r): defaults ride
        # the MESH (an interconnect property), a per-session kwarg
        # overrides.  PYTHON-static like route=: the mode selects which
        # ops the decode body traces — "none" traces the GSPMD fp32
        # all-reduce exactly as today (byte-identity, test-pinned),
        # "int8" traces the explicit two-stage quantized reduction at
        # the row-parallel seams; either way the executable set and the
        # exactly-two-compiles contract are untouched
        from ..distributed import qcollectives as _qc

        if collective_quant is None:
            collective_quant = getattr(mesh, "collective_quant", "none") \
                if mesh is not None else "none"
        if collective_quant_scale is None:
            collective_quant_scale = getattr(
                mesh, "collective_quant_scale", "block") \
                if mesh is not None else "block"
        self.collective_quant = _qc.normalize_collective_quant(
            collective_quant)
        self.collective_quant_scale = _qc.normalize_collective_scale(
            collective_quant_scale)
        if self.collective_quant != "none" and mesh is None:
            raise InvalidArgumentError(
                "collective_quant=%r needs a DecodeMesh: the quantized "
                "collectives replace the mp-axis all-reduces, and an "
                "unsharded session has none (pass mesh=DecodeMesh(dp, "
                "mp) or collective_quant='none')"
                % (self.collective_quant,))
        # populated at trace time by the seam's byte sink (collective
        # bytes of ONE decode step); mp == 1 meshes never install the
        # seam, so "int8" there is a documented no-op
        self._collective_trace: Optional[dict] = None
        if not hasattr(model, "gen_decode_cache"):
            raise InvalidArgumentError(
                "DecodeSession needs a model with gen_decode_cache() and "
                "forward(..., cache=...) (e.g. models.TransformerLM); got %r"
                % type(model).__name__)
        if getattr(model, "causal", True) is False:
            # fail at construction; gen_decode_cache would also refuse,
            # but only inside the first prefill trace
            raise InvalidArgumentError(
                "DecodeSession requires a causal model (got "
                "causal=False): bidirectional encoders cannot decode "
                "incrementally")
        self._model = model
        self._binding = _StateBinding(model)
        self.max_len = int(max_len)
        pos_table = getattr(getattr(model, "position_embeddings", None),
                            "weight", None)
        if pos_table is not None and self.max_len > pos_table.shape[0]:
            # past the table, the jitted gather silently CLAMPS position
            # indices to the last row — wrong logits with no diagnostic
            raise InvalidArgumentError(
                "max_len=%d exceeds the model's position-embedding table "
                "(max_position=%d); positions past the table would "
                "silently reuse its last row" % (max_len,
                                                pos_table.shape[0]))
        bks = list(buckets) if buckets is not None \
            else default_buckets(self.max_len)
        self.buckets = sorted(int(b) for b in bks if b <= self.max_len)
        if not self.buckets:
            raise InvalidArgumentError(
                "no prefill bucket <= max_len=%d (got %r)" % (max_len, bks))
        # session-level DEFAULTS only (docs §5q): the traced bodies never
        # read these — sampling config rides each call as SamplingState
        # vectors, so per-request overrides (the pool's submit params)
        # share the same two executables
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        # fail at construction, not at first trace
        check_sampling(temperature, top_p)
        from ..nn.layer.transformer import normalize_cache_dtype

        # fail at construction with the supported set named, not as a
        # shape/astype error deep in the first prefill trace.  "int8"
        # selects the quantized cache: K/V stored int8 with per-head
        # fp32 scales as extra donated carry leaves in the same pytree
        # — the exactly-two-compiles contract is unchanged, the bytes
        # the decode step streams from HBM per token drop ~4x (fp32)
        # while greedy output stays token-identical over the pinned
        # short-horizon corpus (tests/test_quant_cache.py).
        self._cache_dtype = normalize_cache_dtype(cache_dtype)
        if cache_layout == "recurrent" and self._cache_dtype != "float32":
            # mirror SSMLM.gen_decode_cache's refusal at construction,
            # not inside the first prefill trace: the carry is the
            # exact serving state, so quantizing it changes tokens
            raise InvalidArgumentError(
                "cache_layout='recurrent' supports only "
                "cache_dtype='float32' (got %r): the recurrence carry "
                "is the exact decode state, not a re-read cache"
                % (cache_dtype,))
        # "dense" preallocates [B, H, max_len, D] per row; "paged" stores
        # K/V in fixed-size blocks addressed through a block table
        # (identity-mapped here — the aligned batch needs no allocator;
        # inference.GenerationPool runs a real free-list over the same
        # layout); "recurrent" is the O(1)-state carry of SSM decoders
        # (nn.ssm.SSMLM).  All compile exactly two functions per bucket
        # and are token-identical under greedy decoding.
        from .cache import get_layout, layout_of

        self._layout = get_layout(cache_layout)
        supported = getattr(model, "cache_layouts", ("dense", "paged"))
        if self._layout.name not in supported:
            # fail at construction naming both sides; gen_decode_cache
            # would also refuse, but only inside the first prefill trace
            raise InvalidArgumentError(
                "model %s supports cache_layouts=%r, not %r: positional "
                "K/V layouts ('dense'/'paged') belong to attention "
                "models, 'recurrent' to constant-state models like "
                "nn.ssm.SSMLM"
                % (type(model).__name__, tuple(supported),
                   self._layout.name))
        if int(block_size) < 1:
            raise InvalidArgumentError(
                "block_size must be >= 1, got %r" % (block_size,))
        self.cache_layout = cache_layout
        self.block_size = int(block_size)
        # ``cache_layout`` is what the caller chose; what the hooks
        # dispatch on is the ENTRIES the model hands out for it: one kind
        # throughout gives the registered singleton back, a model that
        # mixes kinds (K/V in some layers, a recurrent state in others) a
        # layout composed of its entries' own (jit.cache.layout_of)
        self._layout = layout_of(jax.eval_shape(
            lambda: model.gen_decode_cache(
                1, self.max_len, self._cache_dtype, layout=cache_layout,
                block_size=self.block_size)))
        if donate is None:
            donate = jax.default_backend() != "cpu"
        # argnum 2 = the cache pytree: every decode step consumes its
        # input cache and returns the successor, so donation is safe by
        # construction (generate() never touches a stale cache)
        self._prefill_jit = jax.jit(self._prefill)
        self._decode_jit = jax.jit(self._decode,
                                   donate_argnums=(2,) if donate else ())
        # compilation routes through the AOT path (jit.aot.AotFunction:
        # lower().compile() + the artifact's cost/memory attribution).
        # The executable-cache keys name the ONE argument whose shape
        # varies — the padded prompt for prefill (batch x bucket), the
        # token vector for decode (batch) — because the weights and the
        # cache are shape-fixed per session; compile counting
        # (_cache_size) and donation semantics are unchanged
        self._prefill_jit = aot.AotFunction(
            self._prefill_jit,
            key_fn=lambda p, b, ids, *r: aot.shape_key(ids),
            name="prefill")
        self._decode_jit = aot.AotFunction(
            self._decode_jit,
            key_fn=lambda p, b, cache, tok, *r: aot.shape_key(tok),
            name="decode",
            meta_fn=lambda p, b, cache, *r: {
                "kv_cache_bytes": aot.kv_arg_bytes(cache)})

    # -- traced bodies ---------------------------------------------------
    @contextlib.contextmanager
    def _collective_seam(self):
        """The ambient quantized-collective seam for one DECODE trace
        region (distributed.qcollectives, docs §5r).  Installed only
        when the mesh has an mp axis to quantize over; mode "none"
        installs the recording-only form — the traced ops are exactly
        the GSPMD path's, but the dense wire bytes still land in the
        sink so the comparison column exists.  The sink is published to
        ``_collective_trace`` after the region so a partial trace never
        leaves half-recorded figures behind."""
        if self.mesh is None or self.mesh.mp == 1:
            yield
            return
        from ..distributed import qcollectives as _qc

        rec = {"mode": self.collective_quant,
               "scale_mode": self.collective_quant_scale,
               "calls": 0, "wire_bytes": 0, "dense_bytes": 0, "tokens": 0}
        with _qc.collective_quant(self.collective_quant, self.mesh,
                                  scale_mode=self.collective_quant_scale,
                                  sink=rec):
            yield
        self._collective_trace = rec

    def _run_model(self, param_vals, buf_vals, ids, cache, adapter=None,
                   collective_seam: bool = False, last=None):
        """One cached forward with the session's weights swapped in.

        Decode is ALWAYS inference: the training flag is forced off for
        the duration of the trace (and restored after), so a session
        owned by a training loop neither samples with dropout nor — the
        nastier failure — silently flips the shared model to eval mode
        as a constructor side effect.

        ``adapter`` (a traced [B] id vector, or None for base-only)
        becomes the ambient per-row LoRA selection for the forward
        (``nn.lora.adapter_ids``): every bank-attached Linear under the
        stack gathers its delta rows by it — models without a bank
        no-op, so the draft model of a speculative pair needs nothing."""
        from ..nn.lora import adapter_ids
        from ..ops.flash_attention import decode_route

        binding = self._binding
        saved = binding.swap_in(param_vals, buf_vals)
        modes = [l.training for l in binding.sublayers]
        for l in binding.sublayers:
            l.training = False
        try:
            # the session's route is ambient for the trace: every
            # decode-attention call under the layer stack (this
            # session's steps AND the pool/speculative bodies that call
            # _run_model) routes by it without a kwarg through forward.
            # ``collective_seam`` opts a DECODE body into the quantized
            # mp-collective seam the same way (prefill stays dense)
            seam = self._collective_seam() if collective_seam \
                else contextlib.nullcontext()
            # ``last``: the one position whose logits the caller wants,
            # for a model that can leave the others out (``logits_at``)
            only = {} if last is None else {"last": last}
            with decode_route(self.route), adapter_ids(adapter), seam:
                logits, new_cache = self._model(
                    Tensor(ids, stop_gradient=True), cache=cache, **only)
            raw = logits.value if isinstance(logits, Tensor) else logits
        finally:
            for l, t in zip(binding.sublayers, modes):
                l.training = t
            binding.swap_out(saved)
        return raw, new_cache

    def _sample(self, logits, samp: SamplingState):
        """One per-row draw under the as-data config; advances each
        row's draw counter (the traced bodies never read the session's
        scalar defaults — that would bake them into the executable)."""
        with jax.named_scope("sample"):
            tok = sample_logits_data(logits, samp.temperature, samp.top_k,
                                     samp.top_p, samp.seed, samp.step)
        return tok, samp._replace(step=samp.step + jnp.uint32(1))

    def _prefill(self, param_vals, buf_vals, ids, true_len, samp):
        """(cache, first_token, samp') from a bucket-padded prompt.

        The cache is built INSIDE the trace (zeros fused away by XLA) and
        its index reset to ``true_len``: pad positions' K/V stay in the
        buffer but are never attended, and the next decode write lands at
        ``true_len``, overwriting pad garbage first.
        """
        b = ids.shape[0]
        true_len = jnp.asarray(true_len, jnp.int32)
        cache = self._model.gen_decode_cache(
            b, self.max_len, self._cache_dtype,
            layout=self.cache_layout, block_size=self.block_size)
        # layout prep BEFORE the forward (jit.cache): identity for the
        # positional layouts; the recurrent layout narrows its update
        # window to the true length so pad positions are identity steps
        cache = self._layout.begin_prefill(cache, true_len)
        # a model that declares ``logits_at`` runs its head on the last
        # real position alone: the logits of a whole 4096-position
        # bucket over a 151,936-wide vocabulary are 1.2 GB that only
        # this one row is read from
        at_last = getattr(self._model, "logits_at", False)
        logits, cache = self._run_model(
            param_vals, buf_vals, ids, cache, samp.adapter,
            last=true_len - 1 if at_last else None)
        cache = self._layout.finalize_prefill(cache, true_len,
                                              self.max_len)
        last = logits[:, 0] if at_last else jax.lax.dynamic_index_in_dim(
            logits, true_len - 1, axis=1, keepdims=False)  # [B, V]
        tok, samp = self._sample(last, samp)
        return cache, tok, samp

    def _decode(self, param_vals, buf_vals, cache, tok, samp):
        """One token in, one token out — the steady-state serving step."""
        logits, cache = self._run_model(param_vals, buf_vals,
                                        tok[:, None], cache, samp.adapter,
                                        collective_seam=True)
        tok, samp = self._sample(logits[:, 0], samp)
        return cache, tok, samp

    # -- host API --------------------------------------------------------
    def _bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if b >= length:
                return b
        # name the available buckets: the caller can act on this from
        # the exception alone (shorten the prompt, or construct the
        # session/pool with a bucket >= the prompt length)
        raise InvalidArgumentError(
            "prompt length %d exceeds the largest prefill bucket %d "
            "(available buckets: %s, max_len=%d); shorten the prompt or "
            "construct the session/pool with buckets=[..., %d] (any "
            "bucket >= the prompt length, capped by max_len)"
            % (length, self.buckets[-1], self.buckets, self.max_len,
               length))

    def _state_vals(self):
        return ([p._value for p in self._binding.params],
                [b._value for b in self._binding.buffers])

    def sampling_state(self, batch: int, seed=None, temperature=None,
                       top_k=None, top_p=None, adapter=0) -> SamplingState:
        """A ``[batch]`` :class:`SamplingState` from the session's
        defaults, any of them overridden per call — the host-side seam
        the pool uses to give every request its own config over the
        same executables."""
        return make_sampling_state(
            batch,
            self.temperature if temperature is None else temperature,
            self.top_k if top_k is None else top_k,
            self.top_p if top_p is None else top_p,
            seed=seed, adapter=adapter)

    def prefill(self, input_ids, sampling: Optional[SamplingState] = None):
        """Run the bucketed prefill; (cache, first_token [B] np, samp')
        where ``samp'`` is the per-row sampling state advanced past the
        prefill draw — thread it into ``_decode_jit`` exactly as the
        returned cache."""
        ids = np.asarray(getattr(input_ids, "value", input_ids))
        if ids.ndim == 1:
            ids = ids[None]
        b, t = ids.shape
        if t < 1:
            # an empty prompt would sample from a clamped position -1
            # over an all-pad bucket: silent garbage, so refuse loudly
            raise InvalidArgumentError(
                "prompt must contain at least one token")
        bucket = self._bucket_for(t)
        padded = np.zeros((b, bucket), ids.dtype)
        padded[:, :t] = ids
        samp = self.sampling_state(b) if sampling is None else sampling
        params, bufs = self._state_vals()
        cache, tok, samp = self._prefill_jit(
            params, bufs, jnp.asarray(padded), jnp.asarray(t, jnp.int32),
            samp)
        return cache, tok, samp

    def generate(self, input_ids, max_new_tokens: int, seed=None,
                 eos_id: Optional[int] = None):
        """Autoregressive generation; np.int32 [B, max_new_tokens].

        1 prefill dispatch + N-1 decode dispatches, zero recompilation
        after the first call per bucket.  ``seed`` fixes the sampling
        streams (row r draws under ``seed + r``; greedy ignores it);
        with ``eos_id``, rows past their EOS are padded with it and the
        loop stops early once every row finished.
        """
        ids = np.asarray(getattr(input_ids, "value", input_ids))
        if ids.ndim == 1:
            ids = ids[None]
        t = ids.shape[1]
        if max_new_tokens < 1:
            raise InvalidArgumentError(
                "max_new_tokens must be >= 1, got %r" % (max_new_tokens,))
        if t + max_new_tokens > self.max_len:
            raise InvalidArgumentError(
                "prompt %d + max_new_tokens %d exceeds cache max_len %d"
                % (t, max_new_tokens, self.max_len))
        samp = self.sampling_state(ids.shape[0], seed=seed)
        cache, tok, samp = self.prefill(ids, samp)
        params, bufs = self._state_vals()
        if eos_id is None:
            # dispatch the WHOLE loop before fetching anything: the token
            # feeds back on-device, so the host never blocks a step; the
            # final jax.device_get starts every transfer async before
            # blocking, so N tokens cost ~one round trip, not N (a
            # blocking per-step fetch would serialize the loop on
            # host-RTT over a thin transport)
            dev_toks = [tok]
            for _ in range(max_new_tokens - 1):
                cache, tok, samp = self._decode_jit(params, bufs, cache,
                                                    tok, samp)
                dev_toks.append(tok)
            return np.stack(jax.device_get(dev_toks),
                            axis=1).astype(np.int32)
        # EOS path: the per-step fetch IS the early-stop signal
        host_tok = np.asarray(tok)
        done = host_tok == eos_id
        toks = [host_tok]
        for _ in range(max_new_tokens - 1):
            if bool(done.all()):
                break
            cache, tok, samp = self._decode_jit(params, bufs, cache, tok,
                                                samp)
            # rows already past their EOS emit eos_id, not the model's
            # continuation (the step still runs for unfinished rows)
            host_tok = np.where(done, eos_id,
                                np.asarray(tok)).astype(np.int32)
            done = done | (host_tok == eos_id)
            toks.append(host_tok)
        out = np.stack(toks, axis=1).astype(np.int32)
        if out.shape[1] < max_new_tokens:
            pad = np.full((out.shape[0], max_new_tokens - out.shape[1]),
                          eos_id, np.int32)
            out = np.concatenate([out, pad], axis=1)
        return out

    def compile_counts(self) -> dict:
        """{'prefill': n_bucket_compilations, 'decode': n} — each cache
        entry of the two jitted callables is one XLA compilation, the
        observable contract behind 'exactly two compiles per bucket'."""
        return {"prefill": int(self._prefill_jit._cache_size()),
                "decode": int(self._decode_jit._cache_size())}

    def cost_report(self) -> dict:
        """Per-executable cost/memory attribution read off the compiled
        artifacts (``jit.aot``): ``{"prefill": {key: entry}, "decode":
        {key: entry}}`` where each entry carries the optimized HLO's
        FLOPs / bytes-accessed, the ``memory_analysis()`` HBM breakdown,
        and (decode) the cache argument's ``kv_cache_bytes``.  A read of
        compile-time analysis — never a compile or a sync."""
        return {"prefill": self._prefill_jit.cost_report(),
                "decode": self._decode_jit.cost_report()}

    def cost_version(self) -> int:
        """Monotonic fingerprint of the executable set (total AOT
        compilations): consumers re-read ``cost_report()`` only when
        this moves, so steady-state polling costs two int reads."""
        return self._prefill_jit.compiles + self._decode_jit.compiles

    def collective_report(self) -> dict:
        """Per-token wire bytes of the decode step's mp-axis activation
        collectives, derived from the shapes the seam recorded at trace
        time (distributed.qcollectives, docs §5r) — never measured,
        never faked.  ``collective_bytes_per_token`` is what the traced
        mode actually moves; ``collective_dense_bytes_per_token`` is the
        fp32 ring equivalent (equal under mode "none", strictly below it
        under "int8" — test-pinned).  ``{}`` before the decode body's
        first trace, off-mesh, or at mp == 1 (no mp collectives
        exist)."""
        rec = self._collective_trace
        if not rec or not rec.get("tokens"):
            return {}
        t = float(rec["tokens"])
        return {
            "collective_quant": self.collective_quant,
            "collective_quant_scale": self.collective_quant_scale,
            "collective_bytes_per_token": rec["wire_bytes"] / t,
            "collective_dense_bytes_per_token": rec["dense_bytes"] / t,
            "collective_calls_per_step": int(rec["calls"]),
            "collective_basis": "per-device ring wire bytes of the "
                                "decode step's row-parallel reductions "
                                "(from traced collective shapes) over "
                                "the per-device tokens the step "
                                "commits",
        }
