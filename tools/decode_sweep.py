"""Decode-engine batch/bucket sweep: where does tokens/s/chip saturate?

The decode step is bandwidth-bound (each token re-reads the whole KV
cache plus the weights), so throughput scales with batch until the cache
reads dominate HBM; the prefill is compute-bound and scales with bucket
length.  This sweep measures both axes of ``jit.DecodeSession``:

- per-token decode time at batch x cache-length points (the marginal
  t(N_tokens) discipline: a 1-token generation
  isolates the prefill term, differences isolate pure decode);
- prefill latency per bucket (one compile per bucket — the compile
  counts are recorded so a bucket-policy regression is visible in the
  report).

- dense-vs-paged per-token decode time with a BLOCK-SIZE axis
  (16/32/64/128 by default): the paged block-table cache trades a
  gather per step for HBM that scales with actual tokens; the sweep
  prints both layouts' tokens/s and reachable-KV-bytes columns so the
  crossover (if any) is measured, not asserted.

- fp32-vs-int8 per-token decode time with a CACHE-DTYPE axis
  (``--cache-dtypes``, both by default): the quantized cache streams
  ~4x fewer bytes per step (int8 K/V + riding fp32 per-head scales);
  tok/s and bytes columns for dense AND paged, so the bandwidth win is
  measured where it is claimed to live.

- a PROMPT-REUSE axis (``--prompt-reuse 0.0 0.5 0.9``): at each
  fraction f, f of the prompts share one common prefix and the rest are
  cold; the paged pool runs with prefix sharing + chunked prefill and
  every row records its measured hit-rate column next to tok/s — so
  the "shared system prompts make serving cheaper" claim carries its
  own evidence of how often the index actually fired.

- a ROUTE axis (``--route auto composition pallas-interpret``): the
  same sessions forced down the XLA composition vs the fused pallas
  decode kernel (docs/DESIGN.md §5l), with compiler bytes/token and
  bandwidth-utilization columns per row — the measurement that
  replaces the DECODE_FLASH_MIN_CACHE crossover guess (on TPU the
  forced route runs the compiled kernel; off-TPU it runs the pallas
  interpreter, which the route name says out loud).

- a MODEL-CLASS axis (``--model-class transformer ssm``): the ssm
  rows serve an ``SSMLM`` (docs/DESIGN.md §5p) at the transformer
  sweep's hidden/layer geometry through the SAME ``DecodeSession`` and
  the SAME marginal recipe, with a state-bytes-per-slot column next to
  the dense K/V bytes the same slot would pin at that cache length —
  and since the carry is O(1), the tok/s rows should read ~flat across
  the cache-length axis, which is itself the measurement.

- an ADAPTERS axis (``--adapters N``): batched multi-LoRA rows
  (docs/DESIGN.md §5q) serve a bank-attached model at the same
  geometry through the SAME ``DecodeSession`` and the SAME marginal
  recipe, with every batch row pinned round-robin to a different
  fine-tune by per-row adapter ids riding the ``SamplingState`` as
  traced data; an ``adapters=0`` baseline row rides along, each row
  records tok/s next to ``adapter_bank_bytes``, and the per-bucket
  compile counts are stamped so an id that leaked into a compiled
  constant shows up as a count, not a vibe.

- a COLLECTIVE-QUANT axis (``--collective-quant none int8``, riding
  the ``--mesh`` legs): each mp>1 mesh point re-runs with the decode
  step's mp-axis all-reduces replaced by the block-int8 two-stage
  collectives (docs/DESIGN.md §5r), and every mesh row records its
  ``collective_bytes_per_token`` (computed from the traced collective
  shapes) next to tok/s.  Off-TPU the tok/s delta times the EMULATED
  mesh — forced host devices share one memory bus, so there is no
  interconnect to save and the run says so out loud (the
  ``pallas-interpret`` discipline); the byte columns are the portable
  measurement.

- plain-vs-SPECULATIVE tokens/s with a ``--speculate K`` axis: the
  draft/verify pool (``inference.SpeculativePool``, K draft tokens per
  round against a 1-layer draft twin) timed against the plain pool at
  the same batch; every speculative leg writes its tok/s AND its
  measured acceptance-rate column to the report, so a speculative
  number can never be read without knowing how many drafts landed.

Run: python tools/decode_sweep.py [--batches 1 2 4 8] [--buckets 128 256 512]
     [--gen 64] [--block-sizes 16 32 64 128]
     [--cache-dtypes float32 int8] [--speculate K]
     [--route auto composition pallas-interpret]
     [--prompt-reuse f ...] [--model-class transformer ssm]
     [--adapters N] [--mesh DP,MP ...] [--collective-quant none int8]
     [--cpu-smoke]
     [--out decode_sweep.json]
Writes the JSON report to --out (default: decode_sweep.json in the
CWD — never into tools/, a measurement artifact is not source);
prints one line per leg.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

REPEATS = 3  # median-of-N


def sweep(pt, cfg, batches, buckets, gen, block_sizes, cache_dtypes,
          routes):
    from bench import measure_decode_marginal  # THE shared timing recipe
    from paddle_tpu.inference.generation import kv_reachable_bytes
    from paddle_tpu.jit import DecodeSession
    from paddle_tpu.models import TransformerLM

    pt.seed(0)
    model = TransformerLM(**cfg, dropout=0.0)
    rng = np.random.RandomState(0)
    legs = []
    compiles = {}
    for bucket in buckets:
        # one session PER bucket with max_len = bucket + gen: the dense
        # decode step always scans the full max_len cache, so a shared
        # max(buckets)-sized session would make every bucket leg measure
        # the SAME cache length and the cache-length axis would be
        # fiction.  The paged sessions add the BLOCK-SIZE axis on top
        # (same cache length, different gather/scatter granularity) and
        # the CACHE-DTYPE axis multiplies both: fp32 vs quantized int8,
        # same math up to quantization error, ~4x fewer bytes per step.
        # The ROUTE axis multiplies again: composition vs the fused §5l
        # pallas kernel (forced both ways), so the crossover constant
        # DECODE_FLASH_MIN_CACHE can be replaced by a measurement —
        # find the cache length where the pallas rows' tok/s pass the
        # composition rows' and set the constant there.
        max_len = bucket + gen
        dims = dict(max_len=max_len, num_layers=cfg["num_layers"],
                    num_heads=cfg["num_heads"],
                    head_dim=cfg["hidden_size"] // cfg["num_heads"])
        sessions = []
        for route_name in routes:
            # "pallas-interpret" names the off-TPU truth honestly: the
            # forced kernel route runs the pallas INTERPRETER off-TPU,
            # so its wall time measures the interpreter, not the chip
            route = ("pallas" if route_name == "pallas-interpret"
                     else route_name)
            for dtype in cache_dtypes:
                sessions.append(("dense", 0, dtype, route_name,
                                 DecodeSession(
                                     model, max_len=max_len,
                                     buckets=[bucket],
                                     cache_dtype=dtype, route=route)))
                for bs in block_sizes:
                    sessions.append(("paged", bs, dtype, route_name,
                                     DecodeSession(
                                         model, max_len=max_len,
                                         buckets=[bucket],
                                         cache_layout="paged",
                                         block_size=bs,
                                         cache_dtype=dtype,
                                         route=route)))
        for batch in batches:
            ids = rng.randint(0, cfg["vocab_size"],
                              (batch, bucket)).astype("int32")
            for layout, bs, dtype, route_name, sess in sessions:
                m = measure_decode_marginal(sess, ids, gen,
                                            repeats=REPEATS)
                kv_bytes = kv_reachable_bytes(
                    [max_len] * batch, layout=layout,
                    block_size=(bs or 32), dtype=dtype, **dims)
                tps = batch / m["per_token_s"]
                cost = sess._decode_jit.last_cost() or {}
                nbytes = cost.get("bytes_accessed")
                bpt = None if nbytes is None else nbytes / batch
                leg = dict(m, batch=batch, prefill=bucket, generated=gen,
                           cache_len=max_len, cache_layout=layout,
                           cache_dtype=dtype,
                           block_size=bs or None,
                           route=route_name,
                           kv_reachable_bytes=kv_bytes,
                           cost_bytes_per_token=bpt,
                           bandwidth_util_bytes_per_sec=(
                               None if bpt is None
                               else round(tps * bpt, 1)),
                           decode_tokens_per_sec=round(tps, 1))
                legs.append(leg)
                print("bucket %-5d batch %-3d  %-5s bs %-4s %-8s "
                      "%-16s  prefill %.4fs  %.3f ms/tok  %8.1f tok/s"
                      "  %6.2f KV-MiB"
                      % (bucket, batch, layout, bs or "-", dtype,
                         route_name, m["prefill_s"],
                         m["per_token_s"] * 1e3,
                         leg["decode_tokens_per_sec"],
                         kv_bytes / 2**20), flush=True)
        compiles["bucket_%d" % bucket] = {
            "%s%s_%s_%s" % (layout, "_bs%d" % bs if bs else "", dtype,
                            route_name): sess.compile_counts()
            for layout, bs, dtype, route_name, sess in sessions}
    return legs, compiles


def ssm_sweep(pt, cfg, batches, buckets, gen):
    """tok/s AND state-bytes per (bucket, batch) for the O(1)-cache
    model class (docs/DESIGN.md §5p): an ``SSMLM`` at the transformer
    sweep's hidden/layer geometry, served by the same ``DecodeSession``
    through the SAME marginal recipe.  Every row carries its
    ``state_bytes_per_slot`` column next to the dense fp32 K/V bytes
    the SAME slot would pin at that cache length, so the capacity
    claim rides on the row, not in prose.  The cache-length axis is
    vacuous here BY CONSTRUCTION — the carry is O(1) in sequence
    length — so tok/s should read ~flat across buckets, and that
    flatness is the measurement."""
    from bench import measure_decode_marginal  # THE shared timing recipe
    from paddle_tpu.jit import DecodeSession
    from paddle_tpu.nn import SSMLM

    pt.seed(0)
    model = SSMLM(vocab_size=cfg["vocab_size"],
                  hidden_size=cfg["hidden_size"],
                  num_layers=cfg["num_layers"], dropout=0.0)
    state_bytes = cfg["num_layers"] * model.d_state * 4
    rng = np.random.RandomState(0)
    legs = []
    compiles = {}
    for bucket in buckets:
        # one session per bucket, same discipline as sweep(): the
        # recurrent step does NOT scan a cache, but the prefill term
        # is bucket-shaped and the compile counts are per session
        max_len = bucket + gen
        sess = DecodeSession(model, max_len=max_len, buckets=[bucket],
                             cache_layout="recurrent")
        # dense fp32 K/V at this cache length for the same geometry:
        # what one transformer slot would pin (2 = K and V)
        kv_equiv = 2 * cfg["num_layers"] * cfg["hidden_size"] \
            * max_len * 4
        for batch in batches:
            ids = rng.randint(0, cfg["vocab_size"],
                              (batch, bucket)).astype("int32")
            m = measure_decode_marginal(sess, ids, gen, repeats=REPEATS)
            tps = batch / m["per_token_s"]
            legs.append(dict(
                m, batch=batch, prefill=bucket, generated=gen,
                cache_len=max_len, model_class="ssm",
                cache_layout="recurrent", cache_dtype="float32",
                d_state=model.d_state,
                state_bytes_per_slot=state_bytes,
                state_reachable_bytes=state_bytes * batch,
                kv_equiv_bytes_per_slot=kv_equiv,
                slots_per_gb=(1 << 30) // state_bytes,
                decode_tokens_per_sec=round(tps, 1)))
            print("bucket %-5d batch %-3d  ssm   recurrent fp32     "
                  "prefill %.4fs  %.3f ms/tok  %8.1f tok/s"
                  "  state %5.1f KiB/slot (dense-KV %6.2f MiB)"
                  % (bucket, batch, m["prefill_s"],
                     m["per_token_s"] * 1e3, tps,
                     state_bytes / 2**10, kv_equiv / 2**20), flush=True)
        compiles["bucket_%d" % bucket] = sess.compile_counts()
    return legs, compiles


def lora_sweep(pt, cfg, batches, buckets, gen, adapter_counts, rank=4):
    """tok/s per (bucket, batch, adapter-count) for the batched
    multi-LoRA seam (docs/DESIGN.md §5q): a bank-attached
    ``TransformerLM`` served by the SAME ``DecodeSession`` through the
    SAME marginal recipe as every other axis, with every batch row
    pinned to a different fine-tune (round-robin over the bank).
    Adapter ids ride the ``SamplingState`` as per-row traced DATA, so
    the per-(count, bucket) compile counts are recorded and must read
    exactly-two like the plain sweep's — a count that grew with the
    adapter axis means an id leaked into a compiled constant.  Rows
    stamp ``adapter_bank_bytes`` next to tok/s: the marginal slowdown
    vs the ``adapters=0`` baseline rows is the price of the gathered
    delta einsums, and the bank bytes are what it buys (8 fine-tunes
    resident for one base copy).  ``--adapters 0`` rows serve the
    plain un-banked model — the in-run baseline."""
    from bench import measure_decode_marginal  # THE shared timing recipe
    from paddle_tpu.jit import DecodeSession
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import lora

    class _MixedAdapterSession(DecodeSession):
        """The plain session whose sampling-state DEFAULT pins batch
        row r to fine-tune ``(r % N) + 1`` — the mixed-batch shape the
        bank exists for, reached through ``generate()`` so the sweep
        reuses the shared marginal recipe verbatim."""

        def __init__(self, *args, sweep_adapters=0, **kw):
            self._sweep_adapters = int(sweep_adapters)
            super().__init__(*args, **kw)

        def sampling_state(self, batch, **kw):
            if self._sweep_adapters and not np.any(kw.get("adapter", 0)):
                kw["adapter"] = (np.arange(batch, dtype=np.int32)
                                 % self._sweep_adapters) + 1
            return super().sampling_state(batch, **kw)

    rng = np.random.RandomState(0)
    legs = []
    compiles = {}
    for n in adapter_counts:
        pt.seed(0)  # identical base weights across the axis
        model = TransformerLM(**cfg, dropout=0.0)
        bank_bytes = 0
        if n > 0:
            lora.attach_lora(model, n_adapters=n + 1, rank=rank)
            for a in range(1, n + 1):
                lora.load_adapter(model, a,
                                  lora.random_adapter(model, seed=a))
            bank_bytes = lora.adapter_bank_bytes(model)
        for bucket in buckets:
            max_len = bucket + gen
            sess = _MixedAdapterSession(model, max_len=max_len,
                                        buckets=[bucket],
                                        sweep_adapters=n)
            for batch in batches:
                ids = rng.randint(0, cfg["vocab_size"],
                                  (batch, bucket)).astype("int32")
                m = measure_decode_marginal(sess, ids, gen,
                                            repeats=REPEATS)
                tps = batch / m["per_token_s"]
                legs.append(dict(
                    m, batch=batch, prefill=bucket, generated=gen,
                    cache_len=max_len, adapters=n,
                    rank=(rank if n else None),
                    cache_layout="dense", cache_dtype="float32",
                    adapter_bank_bytes=bank_bytes,
                    decode_tokens_per_sec=round(tps, 1)))
                print("bucket %-5d batch %-3d  lora x%-3d rank %-4s "
                      "prefill %.4fs  %.3f ms/tok  %8.1f tok/s"
                      "  bank %6.2f MiB"
                      % (bucket, batch, n, rank if n else "-",
                         m["prefill_s"], m["per_token_s"] * 1e3, tps,
                         bank_bytes / 2**20), flush=True)
            compiles["adapters_%d_bucket_%d" % (n, bucket)] = \
                sess.compile_counts()
    return legs, compiles


def speculative_sweep(pt, cfg, batches, buckets, gen, spec_k):
    """Plain-pool vs speculative-pool tokens/s per (bucket, batch),
    with the measured acceptance rate stamped on every speculative
    row.  The draft is the target geometry at num_layers=1 — the
    structural configuration a deployment would run; with random
    weights its acceptance is ~chance, which the column records
    honestly (the tok/s number means nothing without it)."""
    from paddle_tpu.inference import GenerationPool, SpeculativePool
    from paddle_tpu.models import TransformerLM

    pt.seed(0)
    target = TransformerLM(**cfg, dropout=0.0)
    pt.seed(1)
    draft = TransformerLM(**dict(cfg, num_layers=1), dropout=0.0)
    rng = np.random.RandomState(0)
    legs = []
    for bucket in buckets:
        max_len = bucket + gen
        for batch in batches:
            prompts = [rng.randint(0, cfg["vocab_size"],
                                   (bucket,)).astype("int32")
                       for _ in range(batch)]

            def timed(pool):
                pool.generate([prompts[0]], 2)  # compile + warm
                if hasattr(pool, "reset_acceptance_stats"):
                    pool.reset_acceptance_stats()
                walls = []
                for _ in range(REPEATS):
                    t0 = time.perf_counter()
                    outs = pool.generate(prompts, gen)
                    walls.append(time.perf_counter() - t0)
                toks = sum(len(o) for o in outs)
                return toks / float(np.median(walls))

            plain_tps = timed(GenerationPool(target, max_len,
                                             slots=batch,
                                             buckets=[bucket]))
            spec = SpeculativePool(target, draft, max_len,
                                   spec_k=spec_k, slots=batch,
                                   buckets=[bucket])
            spec_tps = timed(spec)
            rate = spec.acceptance_stats()["acceptance_rate"]
            legs.append(dict(batch=batch, prefill=bucket, generated=gen,
                             spec_k=spec_k, cache_layout="dense",
                             cache_dtype="float32",
                             plain_tokens_per_sec=round(plain_tps, 1),
                             decode_tokens_per_sec=round(spec_tps, 1),
                             speedup_vs_plain=round(
                                 spec_tps / plain_tps, 4),
                             acceptance_rate=round(rate, 4)))
            print("bucket %-5d batch %-3d  speculative K=%d  "
                  "%8.1f tok/s (plain %8.1f)  accept %.3f"
                  % (bucket, batch, spec_k, spec_tps, plain_tps, rate),
                  flush=True)
    return legs


def prefix_reuse_sweep(pt, cfg, batches, buckets, gen, reuse_fracs):
    """Tokens/s AND measured prefix-hit-rate per (bucket, batch, reuse
    fraction): at fraction f, round(f * n) of the prompts open with one
    shared prefix (the bucket's front half) and the rest are cold.  The
    pool runs paged + chunked prefill + prefix sharing, so each row's
    hit-rate column says how often the index fired on exactly the
    traffic the tok/s was measured on.  Submissions are STAGGERED (one
    step between submits, prompt order shuffled): the index holds
    RESIDENT blocks only, so a same-instant burst would admit every
    sharer before the first owner indexed a block and the axis would
    structurally read 0.  batch=1 rows still honestly read ~0 — with
    one slot there is never a resident sharer to hit."""
    from paddle_tpu.inference import GenerationPool
    from paddle_tpu.models import TransformerLM

    pt.seed(0)
    model = TransformerLM(**cfg, dropout=0.0)
    rng = np.random.RandomState(0)
    legs = []
    for bucket in buckets:
        max_len = bucket + gen
        prefix_len = bucket // 2
        block = max(8, prefix_len // 4)
        prefix = rng.randint(0, cfg["vocab_size"],
                             (prefix_len,)).astype("int32")
        for batch in batches:
            n = max(4, 4 * batch)  # enough requests that reuse can fire
            for frac in reuse_fracs:
                shared = int(round(frac * n))
                prompts = []
                for i in range(n):
                    tail = rng.randint(0, cfg["vocab_size"],
                                       (bucket - prefix_len,)) \
                        .astype("int32")
                    if i < shared:
                        prompts.append(np.concatenate([prefix, tail]))
                    else:
                        prompts.append(np.concatenate(
                            [rng.randint(0, cfg["vocab_size"],
                                         (prefix_len,)).astype("int32"),
                             tail]))
                pool = GenerationPool(
                    model, max_len, slots=batch, buckets=[bucket],
                    cache_layout="paged", block_size=block,
                    prefill_chunk_tokens=block * 2,
                    prefix_sharing=True)
                rng.shuffle(prompts)
                pool.generate([prompts[-1]], 2)  # compile + warm
                # the warm request is one query that can never hit;
                # reset so the columns cover the measured traffic only
                pool.reset_prefix_stats()
                t0 = time.perf_counter()
                rids = []
                for p in prompts:
                    rids.append(pool.submit(p, gen))
                    pool.step()
                results = pool.run()
                wall = time.perf_counter() - t0
                outs = [results[r] for r in rids]
                stats = pool.prefix_stats()
                rate = stats["hit_rate"]
                tps = sum(len(o) for o in outs) / wall
                legs.append(dict(
                    batch=batch, prefill=bucket, generated=gen,
                    prompt_reuse=frac, requests=n, block_size=block,
                    prefill_chunk_tokens=block * 2,
                    cache_layout="paged", cache_dtype="float32",
                    prefix_hit_rate=round(rate, 4),
                    prefix_tokens_matched=stats["tokens_matched"],
                    decode_tokens_per_sec=round(tps, 1)))
                print("bucket %-5d batch %-3d  reuse %.2f  hit %.3f  "
                      "%8.1f tok/s"
                      % (bucket, batch, frac, rate, tps), flush=True)
    return legs


def mesh_sweep(pt, cfg, batches, buckets, gen, meshes, block_size,
               cquants=("none",)):
    """Sharded (GSPMD, docs §5k) pool tok/s per (bucket, batch, dp×mp
    mesh) against the in-run unsharded baseline, with PER-SHARD HBM
    columns from the allocator and a scaling-efficiency column
    (measured tok/s ÷ baseline × devices).  Meshes that don't fit the
    device set or the model's head count are skipped out loud.

    ``cquants`` adds the COLLECTIVE-QUANT axis (docs §5r): each mp>1
    mesh point re-runs with the decode-step mp all-reduces replaced by
    the block-int8 two-stage collectives, and every mesh row records
    ``collective_bytes_per_token`` (traced-shape wire bytes) next to
    tok/s.  Off-TPU the tok/s delta times the EMULATED mesh — host
    devices share one memory bus, so there is no interconnect to save;
    the byte columns are the portable measurement, and the run says so
    out loud (the ``--route pallas-interpret`` discipline)."""
    import jax

    from paddle_tpu.inference import GenerationPool
    from paddle_tpu.jit.mesh import DecodeMesh
    from paddle_tpu.models import TransformerLM

    rng = np.random.RandomState(0)
    n_dev = len(jax.devices())
    if any(cq != "none" for cq in cquants) \
            and jax.default_backend() == "cpu":
        print("NOTE: collective-quant rows on CPU time the EMULATED "
              "mesh (forced host devices share one memory bus): the "
              "collective_bytes_per_token columns are traced-shape "
              "facts, the tok/s delta is NOT an interconnect "
              "measurement", flush=True)
    legs = []
    for bucket in buckets:
        max_len = bucket + gen
        for batch in batches:
            prompts = [rng.randint(0, cfg["vocab_size"],
                                   (bucket,)).astype("int32")
                       for _ in range(batch)]
            base_tps = None
            for dp, mp in [(1, 1)] + meshes:
                if dp * mp > n_dev:
                    print("mesh %dx%d skipped: needs %d devices, "
                          "have %d" % (dp, mp, dp * mp, n_dev))
                    continue
                if cfg["num_heads"] % mp:
                    print("mesh %dx%d skipped: mp must divide "
                          "num_heads=%d" % (dp, mp, cfg["num_heads"]))
                    continue
                for cq in cquants:
                    if cq != "none" and mp == 1:
                        # documented no-op: a pure-dp mesh has no
                        # mp-axis collectives to quantize
                        print("collective-quant %s skipped on mesh "
                              "%dx%d: no mp-axis collectives" %
                              (cq, dp, mp))
                        continue
                    slots = batch if batch % dp == 0 \
                        else dp * (-(-batch // dp))
                    # fresh model per pool: weight placement MUTATES
                    # params
                    pt.seed(0)
                    model = TransformerLM(**cfg, dropout=0.0)
                    pool = GenerationPool(
                        model, max_len, slots=slots, buckets=[bucket],
                        cache_layout="paged", block_size=block_size,
                        mesh=None if dp == mp == 1
                        else DecodeMesh(dp, mp, collective_quant=cq))
                    pool.generate(prompts[:1], 2)  # compile + warm
                    walls, toks = [], 0
                    for _ in range(REPEATS):
                        t0 = time.perf_counter()
                        outs = pool.generate(prompts, gen)
                        walls.append(time.perf_counter() - t0)
                        toks = sum(len(o) for o in outs)
                    tps = toks / float(np.median(walls))
                    if dp == mp == 1:
                        base_tps = tps
                        scaling = None
                    else:
                        scaling = round(tps / (base_tps * dp * mp), 4) \
                            if base_tps else None
                    stats = pool.cache_stats()
                    legs.append(dict(
                        batch=batch, prefill=bucket, generated=gen,
                        mesh_dp=dp, mesh_mp=mp, slots=slots,
                        cache_layout="paged", cache_dtype="float32",
                        block_size=block_size,
                        collective_quant=cq,
                        collective_bytes_per_token=stats.get(
                            "collective_bytes_per_token"),
                        collective_dense_bytes_per_token=stats.get(
                            "collective_dense_bytes_per_token"),
                        kv_resident_bytes=stats["pool_bytes"],
                        kv_resident_bytes_per_shard=stats["per_shard"]
                        [0]["pool_bytes"],
                        kv_resident_bytes_per_device=stats.get(
                            "pool_bytes_per_device",
                            stats["pool_bytes"]),
                        decode_tokens_per_sec=round(tps, 1),
                        scaling_efficiency=scaling))
                    cbpt = legs[-1]["collective_bytes_per_token"]
                    print("bucket %-5d batch %-3d  mesh %dx%d  cq %-4s"
                          "  %8.1f tok/s  shard-HBM %6.2f MiB%s%s"
                          % (bucket, batch, dp, mp, cq, tps,
                             legs[-1]["kv_resident_bytes_per_shard"]
                             / 2**20,
                             ("  coll-B/tok %.0f" % cbpt)
                             if cbpt is not None else "",
                             ("  eff %.3f" % scaling)
                             if scaling is not None else ""),
                          flush=True)
    return legs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[128, 256, 512])
    ap.add_argument("--gen", type=int, default=64,
                    help="tokens generated per timed leg")
    ap.add_argument("--block-sizes", type=int, nargs="*",
                    default=[16, 32, 64, 128],
                    help="paged-layout KV block sizes to sweep (an "
                         "empty list measures the dense layout only)")
    ap.add_argument("--cache-dtypes", nargs="+",
                    default=["float32", "int8"],
                    help="KV cache storage dtypes to sweep (int8 = "
                         "quantized cache with per-head fp32 scales)")
    ap.add_argument("--route", nargs="+", default=["auto"],
                    choices=["auto", "composition", "pallas-interpret"],
                    metavar="R",
                    help="decode-attention routes to sweep (auto / "
                         "composition / pallas-interpret): rows record "
                         "tok/s, compiler bytes/token and the "
                         "bandwidth-utilization column per route, so "
                         "the kernel-vs-composition crossover "
                         "(DECODE_FLASH_MIN_CACHE) is a measurement. "
                         "On TPU, pallas-interpret still forces the "
                         "COMPILED kernel; the name flags that off-TPU "
                         "it times the pallas interpreter")
    ap.add_argument("--model-class", dest="model_class", nargs="+",
                    default=["transformer"],
                    choices=["transformer", "ssm"], metavar="C",
                    help="model classes to sweep (transformer and/or "
                         "ssm): ssm rows serve an SSMLM through the "
                         "same DecodeSession with the recurrent O(1) "
                         "carry (docs/DESIGN.md §5p) and record tok/s "
                         "next to state-bytes-per-slot vs the dense "
                         "K/V bytes the same slot would pin")
    ap.add_argument("--prompt-reuse", type=float, nargs="*", default=[],
                    metavar="F",
                    help="also sweep prefix sharing at these reuse "
                         "fractions (each F = fraction of prompts "
                         "opening with one shared prefix; rows record "
                         "hit-rate AND tok/s columns)")
    ap.add_argument("--adapters", type=int, default=0, metavar="N",
                    help="also sweep batched multi-LoRA at N resident "
                         "fine-tunes (0 = off): rows serve a "
                         "bank-attached model through the same "
                         "DecodeSession and the same marginal recipe, "
                         "with every batch row pinned round-robin to a "
                         "different adapter via per-row SamplingState "
                         "ids (docs/DESIGN.md §5q); an adapters=0 "
                         "baseline row rides along, and every row "
                         "records tok/s next to adapter_bank_bytes")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="also sweep the speculative draft/verify pool "
                         "at K draft tokens per round (0 = off); every "
                         "speculative row records tok/s AND its "
                         "measured acceptance rate")
    ap.add_argument("--mesh", nargs="*", default=[], metavar="DP,MP",
                    help="also sweep the GSPMD sharded pool at these "
                         "dp,mp meshes (e.g. --mesh 2,1 2,2); every row "
                         "records tok/s, per-shard HBM, and scaling "
                         "efficiency vs the in-run unsharded baseline. "
                         "With --cpu-smoke, 8 virtual host devices are "
                         "forced so the meshes fit")
    ap.add_argument("--collective-quant", dest="collective_quant",
                    nargs="+", default=["none"],
                    choices=["none", "int8"], metavar="Q",
                    help="mp-axis activation-collective modes to sweep "
                         "on the --mesh legs (docs/DESIGN.md §5r): "
                         "int8 re-runs each mp>1 mesh point with the "
                         "decode all-reduces replaced by block-int8 "
                         "two-stage collectives; every mesh row "
                         "records collective_bytes_per_token (traced "
                         "shapes) next to tok/s.  Off-TPU the tok/s "
                         "delta times the EMULATED mesh — the run "
                         "says so out loud; the byte columns are the "
                         "portable measurement")
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny model on CPU to exercise the harness")
    ap.add_argument("--out",
                    default=os.path.join(os.getcwd(),
                                         "decode_sweep.json"),
                    help="report path (default: decode_sweep.json in "
                         "the CWD; never written into tools/)")
    args = ap.parse_args()

    meshes = []
    for spec in args.mesh:
        try:
            dp, mp = (int(x) for x in spec.split(","))
        except ValueError:
            sys.exit("--mesh entries must be DP,MP (e.g. 2,1), got %r"
                     % spec)
        if dp < 1 or mp < 1:
            sys.exit("--mesh needs dp >= 1 and mp >= 1, got %r" % spec)
        meshes.append((dp, mp))

    from bench import _peak_flops, force_host_devices
    from tools.compile_cache import ensure_compile_cache

    if args.cpu_smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if meshes:
            # must land before jax initializes its backends (below):
            # the dp×mp meshes need multiple devices, and on CPU those
            # are the forced host devices
            force_host_devices(os.environ)

    import jax

    ensure_compile_cache()

    import paddle_tpu as pt
    from paddle_tpu.models import gpt_1p3b_config

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.cpu_smoke:
        sys.exit("no TPU found; refusing to 'measure' CPU")

    cfg = gpt_1p3b_config()
    if args.cpu_smoke:
        cfg.update(num_layers=2, hidden_size=128, num_heads=2,
                   intermediate_size=512, vocab_size=1024,
                   max_position=1024)
        if args.buckets == [128, 256, 512]:
            args.buckets = [32, 64]
        if args.batches == [1, 2, 4, 8]:
            args.batches = [1, 2]
        if args.block_sizes == [16, 32, 64, 128]:
            args.block_sizes = [8, 16]
        args.gen = min(args.gen, 8)
    else:
        cfg.update(num_layers=6)  # the one-chip GPT geometry (bench leg)
    # the marginal recipe differences against a 1-token generation
    args.gen = max(args.gen, 2)

    legs, compiles = [], {}
    if "transformer" in args.model_class:
        legs, compiles = sweep(pt, cfg, args.batches, args.buckets,
                               args.gen, args.block_sizes,
                               args.cache_dtypes, args.route)
    ssm_legs = ssm_compiles = None
    if "ssm" in args.model_class:
        ssm_legs, ssm_compiles = ssm_sweep(pt, cfg, args.batches,
                                           args.buckets, args.gen)
    lora_legs = lora_compiles = None
    if args.adapters > 0:
        lora_legs, lora_compiles = lora_sweep(pt, cfg, args.batches,
                                              args.buckets, args.gen,
                                              [0, args.adapters])
    spec_legs = None
    if args.speculate > 0:
        spec_legs = speculative_sweep(pt, cfg, args.batches,
                                      args.buckets, args.gen,
                                      args.speculate)
    mesh_legs = None
    if meshes:
        mesh_legs = mesh_sweep(pt, cfg, args.batches, args.buckets,
                               args.gen, meshes,
                               block_size=(args.block_sizes or [16])[0],
                               cquants=args.collective_quant)
    reuse_legs = None
    if args.prompt_reuse:
        bad = [f for f in args.prompt_reuse if not 0.0 <= f <= 1.0]
        if bad:
            sys.exit("--prompt-reuse fractions must be in [0, 1], "
                     "got %s" % bad)
        reuse_legs = prefix_reuse_sweep(pt, cfg, args.batches,
                                        args.buckets, args.gen,
                                        args.prompt_reuse)
    report = {"measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
              "backend": jax.devices()[0].device_kind,
              "peak_flops": _peak_flops(jax, on_tpu),
              "model": {k: cfg[k] for k in
                        ("hidden_size", "num_layers", "num_heads",
                         "vocab_size")},
              "repeats": REPEATS,
              "block_sizes": args.block_sizes,
              "cache_dtypes": args.cache_dtypes,
              "routes": args.route,
              "adapters": args.adapters or None,
              "spec_k": args.speculate or None,
              "prompt_reuse": args.prompt_reuse or None,
              "mesh": [list(m) for m in meshes] or None,
              "collective_quant": args.collective_quant,
              "model_class": args.model_class,
              "compile_counts": compiles,
              "ssm_compile_counts": ssm_compiles,
              "lora_compile_counts": lora_compiles,
              "legs": legs,
              "ssm_legs": ssm_legs,
              "lora_legs": lora_legs,
              "speculative_legs": spec_legs,
              "prompt_reuse_legs": reuse_legs,
              "mesh_legs": mesh_legs}
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print("report:", args.out)


if __name__ == "__main__":
    main()
