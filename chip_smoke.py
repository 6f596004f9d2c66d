"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one import of JAX, no child that needs the device.  It
drives the two paths users pay chip time for through their normal entry
points — ``serving.ServingEngine`` behind ``ServingHTTPFrontend`` and
``jit.TrainStep`` — at the full width of the GPT-1.3B / BERT-base
configurations, with random weights made from a seed, and checks what
comes out by the repo's own means.  Phases:

- device   the first device must be a TPU, or the script exits non-zero
           saying what it found (never a CPU run under a device name);
- serve    24-layer width-2048 model: a preempt/spill-to-disk/resume
           round, 16 ``POST /generate`` over loopback, every stream
           ``DONE``, zero recoveries, the compile-count contract, tokens
           against ``DecodeSession.generate`` and cached against
           uncached logits (margin-gated); the optimized program of
           the engine's decode step holds no copy of a K/V pool;
- train    BERT-base bf16 O2 ``TrainStep``, batch 40 x 512, five steps,
           finite falling loss;
- kernels  the fused decode kernels forced by ``route="pallas"`` on a
           two-layer model at the serving geometry: dense and paged,
           fp32 and int8, Lq 1 and 5, against the composition, with the
           TPU custom call found in the compiled decode program; the
           paged kernel's grouped-rows form on a bfloat16 pool at
           sdar-30b-a3b's cache geometry; one
           forward-and-backward step at sequence 8192 through the
           library flash kernel;
- mesh     (four devices or more) the same requests under
           ``DecodeMesh(2, 2)`` and ``(1, 4)``, dense and int8
           collectives, and the BERT steps data-parallel, with shards
           and memory checked on every device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase makes the exit code non-zero.  Step times printed here
are sanity figures labelled with the device; none is a metric.

``--cpu-toy`` exists for ``tests/test_chip_smoke.py`` ONLY: the same
phases at toy width on the CPU backend, kernels under the Pallas
interpreter, and a result line that says ``"platform": "cpu"``.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

import numpy as np

PHASES = ("serve", "train", "kernels", "retention", "mesh")

# fp32 operands pass through the MXU in bf16 (nothing under paddle_tpu/
# sets a matmul precision): 8 significand bits, so two paths that order
# their matmuls differently agree to a few units of 2**-8 relative to
# the values' scale.  Fixed here, before any run, from the dtype.
TOL_TPU = 2.0 ** -5
# the CPU backend multiplies fp32 in fp32
TOL_CPU = 1e-4


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def sizes(toy: bool) -> dict:
    """Every size of the run in one place.  ``toy`` is the CPU test's
    geometry; the other is the chip's."""
    from paddle_tpu.models import bert_base_config, gpt_1p3b_config

    if toy:
        lm = dict(vocab_size=256, hidden_size=64, num_layers=1,
                  num_heads=4, intermediate_size=128, max_position=256,
                  causal=True)
        bert = dict(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=2, intermediate_size=128, max_position=32,
                    causal=False)
        return dict(
            lm=lm, slots=4, block=8, bucket=16, max_len=64,
            requests=6, new_tokens=6, spill_tokens=12,
            bert=bert, train_batch=4, train_seq=32,
            kernel_lm=lm, kernel_slots=2, kernel_max_len=64,
            grouped=dict(slots=2, q_heads=4, kv_heads=2, rows=4, block=8,
                         head_dim=16, table=4),
            flash=dict(vocab_size=256, hidden_size=64, num_layers=1,
                       num_heads=2, intermediate_size=128,
                       max_position=128, causal=True),
            flash_seq=128,
            experts=dict(rows=8, held=4, experts=16, groups=4, kept=2,
                         top_k=2, width=32, size=16),
            retention=dict(slots=2, q_heads=4, kv_heads=2, head_dim=16,
                           chunk_tokens=24, timed_steps=2))
    lm = gpt_1p3b_config()          # 24 layers, width 2048, 16 heads x 128
    # fp32: ~5.3 GB of weights; the paged cache costs ~393 KB per token
    # over 24 layers, so 8 slots x 256 positions is ~0.8 GB — weights,
    # cache and temporaries sit well inside the chip's 16 GB
    return dict(
        lm=lm, slots=8, block=32, bucket=128, max_len=256,
        requests=16, new_tokens=32, spill_tokens=64,
        bert=bert_base_config(), train_batch=40, train_seq=512,
        kernel_lm=dict(lm, num_layers=2), kernel_slots=8,
        kernel_max_len=256,
        # sdar-30b-a3b's cache: 32 query heads on 4 K/V heads of 128,
        # blocks of 128 positions, 32 slots x 20 table entries, a block
        # of 4 query positions
        grouped=dict(slots=32, q_heads=32, kv_heads=4, rows=4, block=128,
                     head_dim=128, table=20),
        # a long-sequence training configuration (head_dim 128)
        flash=dict(vocab_size=32000, hidden_size=1024, num_layers=4,
                   num_heads=8, intermediate_size=4096,
                   max_position=8192, causal=True),
        flash_seq=8192,
        # ax-k1's expert layer as one chip holds it: 12 of 192 experts of
        # 7168 x 2048, 8 groups of 24 of which the 4 best, 8 a token, a
        # decode step's 32 rows
        experts=dict(rows=32, held=12, experts=192, groups=8, kept=4,
                     top_k=8, width=7168, size=2048),
        # brumby-14b's state: 40 query heads on 8 K/V heads of 128, 16
        # slots; 256 positions are two chunks of the prefill scan
        retention=dict(slots=16, q_heads=40, kv_heads=8, head_dim=128,
                       chunk_tokens=256, timed_steps=20))


# ---------------------------------------------------------------------------
# checks shared by the phases (unit-tested on their own)
# ---------------------------------------------------------------------------

def check_serving_outcome(results, metrics: dict, new_tokens: int) -> None:
    """Every stream ``DONE`` with its full token count, and no fault
    absorbed on the way: the engine catches step failures and recovers
    (``ServingEngine._recover``), so a chip-only fault would otherwise
    end in a script that exits 0."""
    for r in results:
        check(r.get("state") == "DONE",
              "request %r ended %r (%s), not DONE"
              % (r.get("request_id"), r.get("state"), r.get("error")))
        check(r.get("new_tokens") == new_tokens
              and len(r.get("tokens", ())) == new_tokens,
              "request %r returned %r tokens, wanted %d"
              % (r.get("request_id"), r.get("new_tokens"), new_tokens))
    for name in ("serving_recoveries_total",
                 "serving_requests_failed_total",
                 "serving_ticks_stalled_total"):
        check(name in metrics, "metric %s missing from /metrics" % name)
        check(metrics[name] == 0,
              "%s = %g: the engine absorbed a fault" % (name, metrics[name]))


def kernel_markers(platform: str) -> tuple:
    """What a fused decode kernel leaves in a compiled program's text.
    On the TPU it is a Mosaic custom call.  Under the interpreter (the
    CPU toy run) the kernel is inlined as plain HLO and only the jit
    scope of the kernel's entry point survives, in the ops' names."""
    if platform == "tpu":
        return ("tpu_custom_call",)
    return ("jit(_paged_call)", "jit(_dense_call)")


def check_kernel_in_program(text: str, platform: str, what: str) -> None:
    """The proof that a forced route ran the kernel is the compiled
    program, not the route string."""
    check(any(m in text for m in kernel_markers(platform)),
          "%s: route='pallas' was asked for but none of %r is in the "
          "compiled decode program" % (what, kernel_markers(platform)))


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)


def pool_shaped_ops(text: str, pool_shape) -> list:
    """``(name, opcode)`` of every instruction in a compiled program's
    text, fused computations included, whose result has the shape
    ``pool_shape`` (any element type, any layout)."""
    dims = ",".join(str(int(n)) for n in pool_shape)
    return [(name, op) for name, shape, op in _HLO_RESULT.findall(text)
            if shape == dims]


def pool_shaped_moves(text: str, pool_shape) -> list:
    """The names of the ``copy`` and ``transpose`` instructions whose
    result has a K/V pool's shape.  The step writes a few rows of a
    donated pool, so every such instruction moves the whole pool for
    nothing: the scatter that kept ``H`` as a window dimension cost four
    a layer (``ops.flash_attention.paged_cache_write``)."""
    return [name for name, op in pool_shaped_ops(text, pool_shape)
            if op in ("copy", "transpose")]


def check_no_pool_moves(text: str, pool_shape, platform: str,
                        what: str) -> None:
    """On the TPU the decode program may not copy or re-lay a pool.  The
    CPU backend donates nothing, so there the step's own copy of each
    pool is counted and said, not judged."""
    moves = pool_shaped_moves(text, pool_shape)
    say("[%s] %d copy/transpose instruction(s) with a K/V pool's shape "
        "%r in the optimized decode program"
        % (what, len(moves), tuple(pool_shape)))
    check(platform != "tpu" or not moves,
          "%s: the decode program moves a whole K/V pool %d time(s): %s"
          % (what, len(moves), ", ".join(moves[:8])))


def check_greedy_against_logits(tokens, logits, gate: float,
                                what: str) -> int:
    """``tokens[i]`` must be the argmax of ``logits[i]`` wherever the
    top-2 margin is at least ``gate``; below it the step is a near-tie
    no decode strategy can promise (examples/10_http_serving.py gates
    the same way).  ``logits`` are teacher-forced on ``tokens``
    themselves, so every step is judged on its own.  Returns how many
    steps the gate let through."""
    top2 = np.sort(logits, axis=-1)[:, -2:]
    gated = (top2[:, 1] - top2[:, 0]) >= gate
    wrong = gated & (logits.argmax(-1) != np.asarray(tokens))
    check(not wrong.any(),
          "%s: token at step %d is not the reference argmax although "
          "the top-2 margin %.3g clears the gate %.3g"
          % (what, int(np.argmax(wrong)),
             float((top2[:, 1] - top2[:, 0])[np.argmax(wrong)]), gate))
    return int(gated.sum())


def check_same_until_near_tie(got, want, margins, gate: float,
                              what: str) -> int:
    """Two numerically different paths must emit the same greedy tokens
    until a step whose reference margin is under ``gate``.  Returns the
    length of the common prefix."""
    got, want = np.asarray(got), np.asarray(want)
    same = got == want
    n = len(want) if same.all() else int(np.argmin(same))
    check(n == len(want) or margins[n] < gate,
          "%s: tokens part at step %d where the top-2 margin %.3g "
          "clears the gate %.3g"
          % (what, n, float(margins[min(n, len(margins) - 1)]), gate))
    return n


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def memory_report(jax) -> list:
    rows = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        rows.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                     "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return rows


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _post_generate(base: str, prompt, max_new: int, rid: str) -> dict:
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": int(max_new),
                         "request_id": rid}).encode(),
        headers={"Content-Type": "application/json"})
    streamed, final = [], None
    with urllib.request.urlopen(req, timeout=600) as resp:
        for line in resp:
            msg = json.loads(line)
            if msg.get("done"):
                final = msg
            else:
                streamed.append(msg["token"])
    if final is None:
        return {"request_id": rid, "state": "NO_TERMINAL_RECORD",
                "tokens": streamed, "new_tokens": len(streamed)}
    check(streamed == final["tokens"],
          "request %s: streamed tokens differ from the terminal record"
          % rid)
    return final


def _traffic(base: str, prompts, max_new: int, tag: str) -> list:
    """All requests at once — more than ``slots`` — so queueing,
    admission and slot reuse run."""
    results = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            results[i] = _post_generate(base, prompts[i], max_new,
                                        "%s-%d" % (tag, i))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append("%s-%d: %r" % (tag, i, e))

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    check(not any(t.is_alive() for t in threads),
          "HTTP client threads still waiting after 900 s")
    check(not errors, "HTTP requests failed: %s" % "; ".join(errors))
    return results


def _prompts(sz: dict, rng) -> list:
    """Several lengths up to the prefill bucket."""
    lens = sorted({max(1, sz["bucket"] // 8), sz["bucket"] // 2,
                   (3 * sz["bucket"]) // 4, sz["bucket"]})
    return [rng.randint(0, sz["lm"]["vocab_size"],
                        (lens[i % len(lens)],)).astype(np.int32)
            for i in range(sz["requests"])]


def check_step_ahead(jax, engine, prompt, new_tokens: int,
                     tag: str) -> None:
    """The host runs one step behind the device (docs/DESIGN.md 5t): on
    a steady tick the pool launches step t+1 with step t still in
    flight (``tick.decode``'s ``ahead`` is 1), so the tick's period is
    the step's device time and not that plus the host's turn.  One
    request in pump mode under the tracer; the period is printed beside
    one step timed alone (launched on an idle device and blocked on), so
    a bring-up on another chip sees a lost overlap at once."""
    pool = engine._pool
    tracer = engine.start_trace()
    try:
        stream = engine.submit(prompt, new_tokens,
                               request_id="%s-ahead" % tag)
        while engine.request_state(stream.request_id) != "DECODING":
            check(engine.pump(1), "the request never reached DECODING")
        engine.pump(2)
        engine.settle()                         # the device is idle now
        t0 = time.perf_counter()
        pool._launch_step(None)
        check(pool._flights, "no step to launch for a decoding request")
        jax.block_until_ready(pool._flights[-1][0])
        alone_ms = (time.perf_counter() - t0) * 1e3
        mark = time.perf_counter()
        done = stream.result()
    finally:
        engine.stop_trace()
    check(done.state == "DONE", "the request ended %r: %s"
          % (done.state, done.error))
    spans = sorted((e for e in tracer.recorder.snapshot()
                    if e.dur_s is not None and e.ts >= mark),
                   key=lambda e: e.ts)
    aheads = [e.meta["ahead"] for e in spans if e.name == "tick.decode"]
    delivers = [e.ts for e in spans if e.name == "tick.deliver"]
    check(len(aheads) >= 4, "only %d launches traced" % len(aheads))
    check(all(a == 1 for a in aheads),
          "a steady tick launched with nothing in flight: ahead %r"
          % (aheads,))
    period_ms = float(np.median(np.diff(delivers))) * 1e3
    say("[%s] host one step behind the device: %d of %d steady launches "
        "made with a step in flight; tick period %.2f ms beside %.2f ms "
        "for one step launched alone and blocked on"
        % (tag, sum(aheads), len(aheads), period_ms, alone_ms))


def serve_requests(jax, model, sz: dict, mesh=None,
                   tag: str = "one-chip") -> dict:
    """One engine over ``model`` (optionally on ``mesh``): the spill
    round in pump mode, then the owned loop behind the HTTP front end.
    Returns the tokens per request so runs can be compared."""
    from paddle_tpu.serving import ServingEngine, ServingHTTPFrontend

    rng = np.random.RandomState(0)
    prompts = _prompts(sz, rng)
    spill_dir = tempfile.mkdtemp(prefix="chip_smoke_spill_")
    engine = ServingEngine(
        model, max_len=sz["max_len"], slots=sz["slots"],
        buckets=[sz["bucket"]], max_queue=4 * sz["requests"],
        cache_layout="paged", block_size=sz["block"],
        spill_tier="disk", spill_dir=spill_dir, mesh=mesh)
    front = None
    try:
        # -- preempt -> spill to disk -> resume: the donated cache is
        # read here (export) and written again (resume), so a stale
        # buffer shows up as "Array has been deleted" or wrong tokens
        t0 = time.perf_counter()
        whole = engine.submit(prompts[0], sz["spill_tokens"],
                              request_id="%s-whole" % tag).result()
        say("[%s] warm-up request (compiles included): %.1f s"
            % (tag, time.perf_counter() - t0))
        check(whole.state == "DONE", "warm-up request ended %r: %s"
              % (whole.state, whole.error))
        victim = engine.submit(prompts[0], sz["spill_tokens"],
                               request_id="%s-victim" % tag)
        while engine.request_state(victim.request_id) != "DECODING":
            check(engine.pump(1), "the victim never reached DECODING")
        engine.pump(sz["spill_tokens"] // 4)    # a few committed tokens
        check(victim.status is None,
              "the victim finished before it could be preempted")
        engine.preempt(victim.request_id)
        spilled = os.listdir(spill_dir)
        check(spilled, "preempt left no spill file in %s" % spill_dir)
        resumed = victim.result()
        stats = engine.spill_stats()
        check(resumed.state == "DONE", "resumed request ended %r: %s"
              % (resumed.state, resumed.error))
        check(stats["preempts_total"] == 1 and stats["resumes_total"] == 1,
              "spill round did not run once: %r" % (stats,))
        check(list(resumed.tokens) == list(whole.tokens),
              "tokens after preempt/spill/resume differ from the "
              "uninterrupted run")
        say("[%s] preempt -> disk spill (%d file(s), %d bytes) -> resume: "
            "%d tokens identical"
            % (tag, len(spilled), stats["spill_bytes_total"],
               len(resumed.tokens)))
        check_step_ahead(jax, engine, prompts[0], sz["spill_tokens"], tag)
        warm_counts = engine.compile_counts()

        # -- the traffic: owned step loop + HTTP over loopback
        engine.start()
        front = ServingHTTPFrontend(engine).start()
        base = "http://%s:%d" % front.address
        t0 = time.perf_counter()
        results = _traffic(base, prompts, sz["new_tokens"], tag)
        wall = time.perf_counter() - t0
        with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
            metrics = parse_metrics(r.read().decode())
        check_serving_outcome(results, metrics, sz["new_tokens"])
        counts = engine.compile_counts()
        check(counts == warm_counts,
              "compiles during traffic: %r -> %r" % (warm_counts, counts))
        # the two-compile contract: one prefill bucket and one batched
        # decode step (plus the slot splice), whatever the traffic
        check(counts == {"prefill": 1, "decode": 0, "pool_decode": 1,
                         "slot_insert": 1},
              "compile counts %r break the two-compile contract"
              % (counts,))
        say("[%s] %d POST /generate x %d tokens: all DONE, 0 recoveries, "
            "0 failed, compiles %r, %.1f s on %s"
            % (tag, len(results), sz["new_tokens"], counts, wall,
               jax.devices()[0].device_kind))
        cache_arrays = [a for c in engine._pool._cache
                        for a in (c.k, c.v)]
        if mesh is None:
            check_no_pool_moves(_decode_program_text(engine._pool),
                                cache_arrays[0].shape,
                                jax.devices()[0].platform, tag)
    finally:
        if front is not None:
            front.shutdown()
        engine.shutdown(drain=False)
        shutil.rmtree(spill_dir, ignore_errors=True)
    return {"prompts": prompts,
            "tokens": [r["tokens"] for r in results],
            "cache_arrays": cache_arrays}


def build_lm(pt, cfg: dict):
    """The serving model from the seed: the same call gives the same
    weights, so the mesh phase rebuilds it instead of holding 5 GB of
    device memory through the phases in between."""
    from paddle_tpu.models import TransformerLM

    pt.seed(0)
    model = TransformerLM(**cfg, dropout=0.0)
    model.eval()
    return model


def reference_logits(pt, model, prompts, tokens) -> list:
    """Per request, the plain uncached forward's logits at every
    generated step, teacher-forced on the request's own tokens — the
    reference each greedy step is judged by.  One batched forward: the
    model is causal, so right-padding to a common length changes no
    logit before the pad."""
    seqs = [np.concatenate([p, t]) for p, t in zip(prompts, tokens)]
    full = np.zeros((len(seqs), max(len(s) for s in seqs)), np.int32)
    for i, s in enumerate(seqs):
        full[i, :len(s)] = s
    with pt.no_grad():
        logits = np.asarray(model(pt.to_tensor(full)).value)
    return [logits[i, len(p) - 1:len(p) - 1 + len(t)]
            for i, (p, t) in enumerate(zip(prompts, tokens))]


def phase_serve(pt, jax, sz: dict, tol: float, state: dict) -> None:
    from paddle_tpu.jit import DecodeSession

    t0 = time.perf_counter()
    model = build_lm(pt, sz["lm"])
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    say("[serve] TransformerLM %d layers x %d wide, %d heads: %.2f B "
        "parameters built in %.1f s"
        % (sz["lm"]["num_layers"], sz["lm"]["hidden_size"],
           sz["lm"]["num_heads"], n_params / 1e9,
           time.perf_counter() - t0))
    run = serve_requests(jax, model, sz)
    prompts, tokens = run["prompts"], run["tokens"]

    # -- every request against the plain uncached forward, same device
    logits = reference_logits(pt, model, prompts, tokens)
    scale = max(1.0, max(float(np.abs(l).max()) for l in logits))
    gate = tol * scale
    gated = sum(check_greedy_against_logits(t, l, gate, "request %d" % i)
                for i, (t, l) in enumerate(zip(tokens, logits)))
    margins = [np.diff(np.sort(l, axis=-1)[:, -2:], axis=-1)[:, 0]
               for l in logits]
    state["serve"] = {"prompts": prompts, "tokens": tokens,
                      "margins": margins, "gate": gate, "scale": scale}
    say("[serve] engine tokens are the uncached forward's argmax on all "
        "%d of %d steps whose top-2 margin clears the gate %.3g "
        "(|logit| max %.3g)"
        % (gated, sum(len(t) for t in tokens), gate, scale))

    # -- one prompt through DecodeSession.generate
    ref = DecodeSession(model, max_len=sz["max_len"],
                        buckets=[sz["bucket"]], cache_layout="paged",
                        block_size=sz["block"])
    want = ref.generate(prompts[0][None], sz["new_tokens"])[0]
    n = check_same_until_near_tie(tokens[0], want, margins[0], gate,
                                  "engine vs DecodeSession.generate")
    say("[serve] engine == DecodeSession.generate on %d of %d tokens "
        "(any parting is at a near-tie)" % (n, len(want)))

    # -- first-token logits: the cached prefill path against the plain
    # forward (the prompt chunk is prefill-shaped, so this is the
    # composition the bucketed prefill runs)
    cache = model.gen_decode_cache(1, sz["max_len"], "float32",
                                   layout="paged",
                                   block_size=sz["block"])
    with pt.no_grad():
        cached, _ = model(pt.to_tensor(prompts[0][None]), cache=cache)
    first_cached = np.asarray(cached.value)[0, -1]
    first_plain = logits[0][0]
    diff = float(np.abs(first_cached - first_plain).max())
    check(first_cached.shape == (sz["lm"]["vocab_size"],),
          "first-token logits have shape %r" % (first_cached.shape,))
    check(np.isfinite(first_cached).all(), "cached logits not finite")
    check(diff <= gate,
          "first-token logits: cached vs uncached differ by %.3g "
          "(> %.3g)" % (diff, gate))
    say("[serve] first-token logits, cached vs uncached: max |diff| %.3g "
        "(tolerance %.3g)" % (diff, gate))
    say("[serve] device memory: %s" % json.dumps(memory_report(jax)[:1]))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def build_train_step(pt, cfg: dict, shift_labels: bool):
    """A training step as a user builds it: AdamW under bf16 O2 (fp32
    master weights) through the donated TrainStep."""
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import TransformerLM, TransformerLMCriterion

    pt.seed(0)
    model = TransformerLM(**cfg, dropout=0.0)
    criterion = TransformerLMCriterion(shift_labels=shift_labels)
    opt = pt.optimizer.AdamW(1e-4, parameters=model.parameters())
    model, opt = pt.amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def loss_fn(m, ids, labels):
        with pt.amp.auto_cast(level="O1", dtype="bfloat16"):
            return criterion(m(ids), labels)

    return model, opt, TrainStep(model, loss_fn, opt)


def run_train_steps(jax, step, ids, tag: str, n_devices: int = 1,
                    n: int = 5) -> list:
    losses = []
    t_after_warm = None
    for i in range(n):
        if i == 2:
            t_after_warm = time.perf_counter()
        loss = step(ids, ids)
        losses.append(float(np.asarray(getattr(loss, "value", loss))))
    dt = (time.perf_counter() - t_after_warm) / (n - 2)
    check(all(np.isfinite(l) for l in losses),
          "%s: loss not finite: %r" % (tag, losses))
    check(losses[-1] < losses[0],
          "%s: loss did not fall over %d steps: %r" % (tag, n, losses))
    say("[%s] %d steps, loss %.4f -> %.4f; step after warm-up %.1f ms on "
        "%d x %s (sanity figure, not a metric)"
        % (tag, n, losses[0], losses[-1], dt * 1e3, n_devices,
           jax.devices()[0].device_kind))
    return losses


def phase_train(pt, jax, sz: dict, tol: float, state: dict) -> None:
    _, _, step = build_train_step(pt, sz["bert"], shift_labels=False)
    rng = np.random.RandomState(0)
    ids = jax.device_put(rng.randint(
        0, sz["bert"]["vocab_size"],
        (sz["train_batch"], sz["train_seq"])).astype("int32"))
    run_train_steps(jax, step, ids, "train")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _decode_program_text(sess) -> str:
    exes = sess._decode_jit._exes
    check(len(exes) == 1, "expected one decode executable, found %d"
          % len(exes))
    return next(iter(exes.values())).as_text()


def _kernel_variant(jax, model, sz: dict, layout: str, dtype: str,
                    tol: float, platform: str) -> None:
    """One cache variant: the real prefill fills the cache, one probe
    executable runs an Lq=1 step and an Lq=5 verify chunk through the
    traced body of a ``route="pallas"`` session and of a
    ``route="composition"`` one, and the pallas session's real decode
    step is compiled and searched for the kernel."""
    import jax.numpy as jnp

    from paddle_tpu.jit import DecodeSession

    what = "%s/%s" % (layout, dtype)
    b = sz["kernel_slots"]
    rng = np.random.RandomState(1)
    vocab = sz["kernel_lm"]["vocab_size"]
    sess, comp = (DecodeSession(
        model, max_len=sz["kernel_max_len"], buckets=[sz["bucket"]],
        cache_layout=layout, block_size=sz["block"], cache_dtype=dtype,
        route=route) for route in ("pallas", "composition"))
    prompt = rng.randint(0, vocab, (b, sz["bucket"] - 3)).astype(np.int32)
    cache, tok, samp = sess.prefill(prompt)
    chunks = [jnp.asarray(rng.randint(0, vocab, (b, lq)), jnp.int32)
              for lq in (1, 5)]

    def probe(params, bufs, cache, chunks):
        return [[s_._run_model(params, bufs, ids, cache)[0]
                 for ids in chunks] for s_ in (sess, comp)]

    params, bufs = sess._state_vals()
    compiled = jax.jit(probe).lower(params, bufs, cache, chunks).compile()
    check_kernel_in_program(compiled.as_text(), platform,
                            what + " probe")
    pal, ref = compiled(params, bufs, cache, chunks)
    worst = 0.0
    for lq, p_, r_ in zip((1, 5), pal, ref):
        p_, r_ = np.asarray(p_), np.asarray(r_)
        check(np.isfinite(p_).all(),
              "%s Lq=%d: kernel logits not finite" % (what, lq))
        scale = max(1.0, float(np.abs(r_).max()))
        diff = float(np.abs(p_ - r_).max())
        worst = max(worst, diff / scale)
        check(diff <= tol * scale,
              "%s Lq=%d: kernel vs composition logits differ by %.3g "
              "(> %.3g)" % (what, lq, diff, tol * scale))
    # the session's real decode step, forced onto the kernel
    cache, tok, samp = sess._decode_jit(params, bufs, cache, tok, samp)
    jax.block_until_ready(tok)
    text = _decode_program_text(sess)
    check_kernel_in_program(text, platform, what)
    if layout == "paged":
        check_no_pool_moves(text, cache[0].k.shape, platform,
                            "kernels " + what)
    say("[kernels] %-13s Lq=1 and Lq=5 compiled under %s, kernel found in "
        "the decode program, max |diff| vs composition %.3g of scale "
        "(tolerance %.3g)"
        % (what, "Mosaic" if platform == "tpu" else "the interpreter",
           worst, tol))


def _grouped_kernel(jax, sz: dict, tol: float, platform: str) -> None:
    """The paged kernel's grouped form on a bfloat16 pool: the query
    heads that share a K/V head are one block of rows, every row of a
    slot sees to the end of its block of positions.  Slots hold
    different lengths, one of them a single block, so the kernel skips
    most of the table; against the composition on the gathered cache."""
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_decode_attention

    g = sz["grouped"]
    b, mb, bs, d = g["slots"], g["table"], g["block"], g["head_dim"]
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, g["q_heads"], g["rows"], d), jnp.bfloat16)
    nb = 1 + b * mb
    k_pool, v_pool = (jnp.asarray(rng.randn(nb, g["kv_heads"], bs, d),
                                  jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)
    ends = rng.randint(1, mb * bs // g["rows"], b) * g["rows"] - 1
    ends[0] = g["rows"] - 1
    q_pos = jnp.asarray(np.repeat(ends[:, None], g["rows"], 1), jnp.int32)

    def both(q, k_pool, v_pool, table, q_pos):
        return [paged_decode_attention(q, k_pool, v_pool, table,
                                       q_pos=q_pos, route=route)
                for route in ("pallas", "composition")]

    compiled = jax.jit(both).lower(q, k_pool, v_pool, table,
                                   q_pos).compile()
    check_kernel_in_program(compiled.as_text(), platform, "grouped/bf16")
    pal, ref = (np.asarray(x.astype(jnp.float32))
                for x in compiled(q, k_pool, v_pool, table, q_pos))
    check(np.isfinite(pal).all(), "grouped/bf16: kernel output not finite")
    # both sides round their float32 sums to bfloat16 at the end: an
    # ulp is up to 2**-7 of a value, so two of them
    tol = max(tol, 2.0 ** -6)
    scale = max(1.0, float(np.abs(ref).max()))
    diff = float(np.abs(pal - ref).max())
    check(diff <= tol * scale,
          "grouped/bf16: kernel vs composition differ by %.3g (> %.3g)"
          % (diff, tol * scale))
    say("[kernels] grouped/bf16   %d query heads on %d K/V heads, %d rows, "
        "blocks of %d: max |diff| vs composition %.3g of scale (tolerance "
        "%.3g)" % (g["q_heads"], g["kv_heads"], g["rows"], bs,
                   diff / scale, tol))


def _expert_routes(jax, sz: dict, tol: float, platform: str) -> None:
    """The expert layer's few-row routes on one draw of the router, in
    bfloat16: only the experts some row chose (``_touched``) against
    every held expert on every row (``_every_expert``)."""
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import moe

    g = sz["experts"]
    rows, held, k = g["rows"], g["held"], g["top_k"]
    shape = (rows, held, g["experts"], k, g["width"], g["size"])
    picked = moe.expert_route(*shape, 2)
    # the toy's experts are too small for a skipped read to pay
    check(picked == ("touched" if g["width"] >= 2048 else "every"),
          "the rule picks %r at rows, held, experts, top_k, width, size "
          "= %r" % (picked, shape))
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (rows, g["width"]), jnp.bfloat16)
    scores = 1.7 * jax.random.normal(ks[1], (rows, g["experts"]),
                                     jnp.float32)
    w_gate, w_up, w_down = (
        (0.02 * jax.random.normal(k_, s, jnp.float32)).astype(jnp.bfloat16)
        for k_, s in zip(ks[2:], [(held, g["width"], g["size"])] * 2
                         + [(held, g["size"], g["width"])]))

    def both(x, scores, w_gate, w_up, w_down):
        gates, experts = moe.route_top_k(scores, k, "sigmoid", g["groups"],
                                         g["kept"], 2.5)
        # this share: the second ``held`` experts of the first group
        local = experts.reshape(-1) - held
        key = jnp.where((local >= 0) & (local < held), local, held)
        touched = jnp.sum(jnp.bincount(key, length=held + 1)[:held] > 0)
        return [route(x, gates, key, held, k, w_gate, w_up, w_down)
                for route in (moe._touched, moe._every_expert)] + [touched]

    got, want, touched = (np.asarray(a, np.float32) for a in
                          jax.jit(both)(x, scores, w_gate, w_up, w_down))
    check(np.isfinite(got).all(), "experts/bf16: touched route not finite")
    # one side sums its experts in float32, the other inside one matmul
    # over bfloat16 products: a bfloat16 ulp of the widest product
    tol = max(tol, 2.0 ** -6)
    scale = max(1.0, float(np.abs(want).max()))
    diff = float(np.abs(got - want).max())
    check(diff <= tol * scale and float(np.abs(want).max()) > 0,
          "experts/bf16: touched vs every expert differ by %.3g (> %.3g)"
          % (diff, tol * scale))
    say("[kernels] experts/bf16   %d rows, %d of %d experts of %d x %d "
        "held, %d touched: the rule picks %r; max |diff| of the touched "
        "route vs every expert %.3g of scale (tolerance %.3g)"
        % (rows, held, g["experts"], g["width"], g["size"], int(touched),
           picked, diff / scale, tol))


def _flash_step(pt, jax, sz: dict, platform: str) -> None:
    """One forward-and-backward step of the long-sequence configuration
    through the library's Pallas flash attention."""
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as flash_op
    from paddle_tpu.ops import flash_attention_supported

    # (paddle_tpu.ops exports the FUNCTION under the module's name)
    _reference_attention = importlib.import_module(
        "paddle_tpu.ops.flash_attention")._reference_attention

    cfg, seq = sz["flash"], sz["flash_seq"]
    heads = cfg["num_heads"]
    d = cfg["hidden_size"] // heads
    shape = (1, heads, seq, d)
    on_kernel = flash_attention_supported(shape, jnp.bfloat16)
    check(on_kernel == (platform == "tpu"),
          "flash gate says %r for %r on %s" % (on_kernel, shape, platform))
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
               for _ in range(3))

    def loss(q, k, v):
        out = flash_op(q, k, v, causal=True)
        return out.astype(jnp.float32).sum(), out

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True)) \
        .lower(q, k, v).compile()
    if platform == "tpu":
        check("tpu_custom_call" in compiled.as_text(),
              "no TPU custom call in the sequence-%d attention step"
              % seq)
    (val, out), grads = compiled(q, k, v)
    check(bool(np.isfinite(np.asarray(val))) and all(
        bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads),
        "flash forward/backward produced non-finite values")
    # causal: the first 512 queries see only the first 512 keys, so a
    # small reference checks the kernel without an [L, L] score matrix
    n = min(512, seq)
    ref = _reference_attention(
        *(x[:, :, :n].astype(jnp.float32) for x in (q, k, v)),
        None, True, 1.0 / float(np.sqrt(d)))
    diff = float(jnp.abs(out[:, :, :n].astype(jnp.float32) - ref).max())
    check(diff <= TOL_TPU,     # bf16 inputs on either backend
          "flash output differs from the reference by %.3g" % diff)

    # the model step of that configuration
    _, _, step = build_train_step(pt, cfg, shift_labels=True)
    ids = jax.device_put(rng.randint(0, cfg["vocab_size"],
                                     (1, seq)).astype("int32"))
    val = float(np.asarray(step(ids, ids).value))
    check(np.isfinite(val), "sequence-%d train step loss %r" % (seq, val))
    say("[kernels] flash seq %d head_dim %d: forward+backward %s, "
        "max |diff| vs reference on the first %d positions %.3g; model "
        "step loss %.4f"
        % (seq, d, "through the TPU custom call" if platform == "tpu"
           else "on the composition (no TPU)", n, diff, val))


def phase_kernels(pt, jax, sz: dict, tol: float, state: dict) -> None:
    import jax.numpy as jnp

    from paddle_tpu.core.errors import InvalidArgumentError
    from paddle_tpu.ops import decode_attention

    platform = jax.devices()[0].platform
    model = build_lm(pt, sz["kernel_lm"])
    for layout in ("dense", "paged"):
        for dtype in ("float32", "int8"):
            _kernel_variant(jax, model, sz, layout, dtype, tol, platform)
    _grouped_kernel(jax, sz, tol, platform)
    _expert_routes(jax, sz, tol, platform)
    # a geometry the kernel cannot take: the forced route must refuse
    # it by name — never decode on the composition instead
    heads = sz["kernel_lm"]["num_heads"]
    d = sz["kernel_lm"]["hidden_size"] // heads
    q = jnp.zeros((1, heads, 1, d), jnp.float32)
    kv = jnp.zeros((1, heads, 520, d), jnp.float32)  # no tile divides 520
    try:
        decode_attention(q, kv, kv, route="pallas")
    except InvalidArgumentError as e:
        say("[kernels] refused by name: %s" % str(e)[:200])
    else:
        raise SmokeFailure("route='pallas' took a cache length with no "
                           "sequence tile instead of refusing it")
    del model
    _flash_step(pt, jax, sz, platform)


# ---------------------------------------------------------------------------
# mesh (four devices or more)
# ---------------------------------------------------------------------------

def phase_retention(pt, jax, sz: dict, tol: float, state: dict) -> None:
    """Gated power retention (``ops/power_retention.py``) at the benchmark
    configuration's head geometry: one prefill of two chunks, as the chunked
    form and from an empty state, and one decode step from the state it
    leaves, each against the quadratic definition;
    the step on the route a server takes here (the Pallas kernel on a TPU)
    and as the XLA composition, both timed."""
    import jax.numpy as jnp

    from paddle_tpu.ops import power_retention as pr

    r = sz["retention"]
    b, hq, hkv, d = r["slots"], r["q_heads"], r["kv_heads"], r["head_dim"]
    t = r["chunk_tokens"]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    q = bf(jax.random.normal(ks[0], (b, hq, t + 1, d))) * d ** -0.5
    k = bf(jax.random.normal(ks[1], (b, hkv, t + 1, d)))
    v = bf(jax.random.normal(ks[2], (b, hkv, t + 1, d)))
    lg = jax.nn.log_sigmoid(
        4.0 + 2.0 * jax.random.normal(ks[3], (b, hkv, t + 1)))
    d_phi = pr.phi_size(d)
    zeros = (jnp.zeros((b, hkv, d, d_phi), jnp.float32),
             jnp.zeros((b, hkv, 1, d_phi), jnp.float32))
    # the definition on two rows (every row runs the same program)
    want = jax.jit(pr.power_retention_quadratic)(q[:2], k[:2], v[:2], lg[:2])
    head = lambda x: x[:, :, :t]
    scale = float(jnp.max(jnp.abs(want)))
    forms = {
        "chunked": lambda: jax.jit(pr.power_retention_chunked)(
            head(q), head(k), head(v), head(lg), *zeros),
        # from an empty state, as a server's prefill: on a TPU the
        # quadratic kernel, elsewhere the chunked form again
        "from empty": lambda: jax.jit(pr.power_retention_prefill)(
            head(q), head(k), head(v), head(lg))}
    states = {}
    for name, form in forms.items():
        y, s, z = form()
        err = float(jnp.max(jnp.abs(y[:2] - want[:, :, :t])))
        say("[retention] %s prefill of %d positions vs the quadratic "
            "form: max |err| %.3g of scale %.3g" % (name, t, err, scale))
        check(err <= tol * scale, "the %s prefill differs from the "
              "quadratic form by %.3g (gate %.3g)"
              % (name, err, tol * scale))
        states[name] = s
    top = float(jnp.max(jnp.abs(states["chunked"])))
    err = float(jnp.max(jnp.abs(states["chunked"] - states["from empty"])))
    check(err <= tol * top, "the two prefills leave states %.3g apart "
          "(gate %.3g)" % (err, tol * top))
    keep = jnp.arange(b) != b - 1       # the last row is a free slot
    last = lambda x: x[:, :, t]
    routes = {}
    for route in ("auto", "composition"):
        step = jax.jit(lambda s_, z_, route=route: pr.power_retention_step(
            last(q), last(k), last(v), last(lg), s_, z_, keep, route=route),
            donate_argnums=(0, 1))
        text = step.lower(s, z).compile().as_text()
        s_in, z_in = jnp.copy(s), jnp.copy(z)
        y1, s1, z1 = step(s_in, z_in)
        err = float(jnp.max(jnp.abs(y1[:1] - want[:1, :, t])))
        check(err <= tol * scale, "the %s step differs from the quadratic "
              "form by %.3g (gate %.3g)" % (route, err, tol * scale))
        check(bool(jnp.all(s1[-1] == s[-1])) and bool(jnp.all(z1[-1]
                                                                == z[-1])),
              "the %s step moved a free slot's state" % route)
        n = r["timed_steps"]
        jax.block_until_ready(s1)
        t0 = time.perf_counter()
        for _ in range(n):
            y1, s1, z1 = step(s1, z1)
        jax.block_until_ready((y1, s1))
        dt = (time.perf_counter() - t0) / n
        moved = 2 * 4 * b * hkv * d_phi * (d + 1)
        routes[route] = dt
        copies = pool_shaped_moves(text, s.shape)
        say("[retention] step, route %s: max |err| %.3g; %.3f ms a call, "
            "%.1f GB/s of the state's 2 x %.1f MB; pool-shaped moves %s"
            % (route, err, 1e3 * dt, moved / dt / 1e9, moved / 2e6,
               copies or "none"))
        check(not copies or jax.devices()[0].platform == "cpu",
              "the %s step copies the state: %s" % (route, copies))
    state["retention_ms"] = {k_: 1e3 * v_ for k_, v_ in routes.items()}


def _check_spans(arrays, devices, what: str) -> None:
    want = {d.id for d in devices}
    for a in arrays:
        have = {s.device.id for s in a.addressable_shards}
        check(have == want,
              "%s: an array of shape %r sits on devices %r, not %r"
              % (what, tuple(a.shape), sorted(have), sorted(want)))


def _check_memory(jax, devices, what: str, floor: int) -> None:
    for d in devices:
        used = (d.memory_stats() or {}).get("bytes_in_use")
        check(used is None or used >= floor,
              "%s: device %d holds %r bytes (< %d): no work there"
              % (what, d.id, used, floor))


def _mesh_serve(jax, model, sz: dict, one_chip: dict, devices, dp: int,
                mp: int, quant: str) -> None:
    from paddle_tpu.jit.mesh import DecodeMesh

    tag = "mesh-%dx%d-%s" % (dp, mp, quant)
    mesh = DecodeMesh(dp=dp, mp=mp, collective_quant=quant)
    run = serve_requests(jax, model, sz, mesh=mesh, tag=tag)
    # jit/mesh.py's axis rules: every weight and cache array has a shard
    # on every device of the mesh (sharded or replicated)
    weights = [p.value for p in model.parameters()]
    _check_spans(weights, devices, tag + " weights")
    _check_spans(run["cache_arrays"], devices, tag + " cache")
    check(any(not w.sharding.is_fully_replicated for w in weights),
          "%s: no weight is sharded over mp" % tag)
    check(all(not a.sharding.is_fully_replicated
              for a in run["cache_arrays"]),
          "%s: a cache array is replicated, not sharded" % tag)
    weight_bytes = sum(w.size * w.dtype.itemsize for w in weights)
    _check_memory(jax, devices, tag, weight_bytes // (2 * mp))
    del run["cache_arrays"]
    # against the one-chip run: partitioned matmuls reduce in another
    # order; a block-int8 all-reduce over mp shards adds up to
    # (mp + 1) / 254 of a block's absmax to what it reduces (each
    # incoming chunk and the sum round once), so the int8 gate takes
    # that fraction of the logit scale on top — fixed beforehand
    gate = one_chip["gate"]
    if quant == "int8":
        gate += (mp + 1) / 254.0 * one_chip["scale"]
    common = [check_same_until_near_tie(
        got, want, m, gate, "%s request %d" % (tag, i))
        for i, (got, want, m) in enumerate(zip(
            run["tokens"], one_chip["tokens"], one_chip["margins"]))]
    say("[%s] %d of %d requests token-identical to the one-chip run, the "
        "rest part at a near-tie (shortest common prefix %d of %d); "
        "bytes in use per device %s"
        % (tag, sum(n == sz["new_tokens"] for n in common), len(common),
           min(common), sz["new_tokens"],
           json.dumps([r["bytes_in_use"]
                       for r in memory_report(jax)[:4]])))


def _mesh_train(pt, jax, sz: dict, devices) -> None:
    """BERT, data-parallel over four devices: weights and optimizer
    state replicated, the batch split over ``dp``; XLA inserts the
    gradient all-reduce from the shardings."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("dp",))
    model, opt, step = build_train_step(pt, sz["bert"],
                                        shift_labels=False)
    rep = NamedSharding(mesh, P())
    for p in model.parameters():
        p._replace_value(jax.device_put(p.value, rep))
    for st in opt._states.values():
        for k, v in st.items():
            st[k] = jax.device_put(v, rep)
    rng = np.random.RandomState(0)
    batch = sz["train_batch"] - sz["train_batch"] % len(devices)
    ids = jax.device_put(
        rng.randint(0, sz["bert"]["vocab_size"],
                    (batch, sz["train_seq"])).astype("int32"),
        NamedSharding(mesh, P("dp")))
    with mesh:
        run_train_steps(jax, step, ids, "mesh-train-dp4",
                        n_devices=len(devices))
    _check_spans([p.value for p in model.parameters()], devices,
                 "mesh-train-dp4 weights")
    _check_memory(jax, devices, "mesh-train-dp4", 1 << 20)
    say("[mesh-train-dp4] bytes in use per device %s"
        % json.dumps([r["bytes_in_use"] for r in memory_report(jax)[:4]]))


def phase_mesh(pt, jax, sz: dict, tol: float, state: dict) -> None:
    check("serve" in state, "the mesh phase compares against the "
          "one-chip serve phase, which did not complete")
    devices = jax.devices()[:4]
    problems = []

    def attempt(what, fn, *args):
        # every configuration runs even if an earlier one failed: a
        # four-chip call is dear, and each failure is its own finding
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001 - collected, then raised
            traceback.print_exc()
            problems.append("%s: %s: %s"
                            % (what, type(e).__name__, str(e)[:200]))
        gc.collect()

    model = build_lm(pt, sz["lm"])     # the serve phase's weights again
    for dp, mp, quant in ((2, 2, "none"), (1, 4, "none"), (1, 4, "int8")):
        attempt("mesh-%dx%d-%s" % (dp, mp, quant), _mesh_serve, jax,
                model, sz, state["serve"], devices, dp, mp, quant)
    del model
    attempt("mesh-train-dp4", _mesh_train, pt, jax, sz, devices)
    check(not problems, "; ".join(problems))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-toy", action="store_true",
                    help="FOR tests/test_chip_smoke.py ONLY: toy widths "
                         "on the CPU backend, kernels interpreted; the "
                         "result says platform cpu")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of %s (default all; "
                         "mesh runs only where four devices are found)"
                         % (PHASES,))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error("unknown phase(s) %s; choose from %s"
                 % (unknown, list(PHASES)))

    import jax
    import jaxlib
    import libtpu

    # -- device ----------------------------------------------------------
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("[device] platform=%s kind=%s count=%d  jax=%s jaxlib=%s "
        "libtpu=%s  JAX_PLATFORMS=%r"
        % (dev.platform, dev.device_kind, len(jax.devices()),
           jax.__version__, jaxlib.__version__, libtpu.__version__,
           os.environ.get("JAX_PLATFORMS")))
    want = "cpu" if args.cpu_toy else "tpu"
    if dev.platform != want:
        print("chip_smoke: needs a %s, but jax.devices()[0].platform is "
              "%r (JAX_PLATFORMS=%r); refusing to run on it"
              % (want.upper(), dev.platform,
                 os.environ.get("JAX_PLATFORMS")), file=sys.stderr)
        return 2

    from tools.compile_cache import CacheCounter, ensure_compile_cache

    cache_dir = ensure_compile_cache()
    cache = CacheCounter()
    say("[cache] compile cache at %s (%s)"
        % (cache_dir, "from JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "the checkout's default"))

    import paddle_tpu as pt

    sz = sizes(args.cpu_toy)
    tol = TOL_CPU if args.cpu_toy else TOL_TPU
    if "mesh" in phases and len(jax.devices()) < 4:
        say("[mesh] skipped: %d device(s) found, the mesh phase needs 4"
            % len(jax.devices()))
        phases.remove("mesh")
    runners = {"serve": phase_serve, "train": phase_train,
               "kernels": phase_kernels, "retention": phase_retention,
               "mesh": phase_mesh}
    failed = {}
    state: dict = {}
    for name in PHASES:
        if name not in phases:
            continue
        t0 = time.perf_counter()
        try:
            runners[name](pt, jax, sz, tol, state)
            say("[%s] ok in %.1f s" % (name, time.perf_counter() - t0))
        except Exception as e:  # noqa: BLE001 - every failure is reported
            traceback.print_exc()
            failed[name] = "%s: %s" % (type(e).__name__, str(e)[:300])
            say("[%s] FAILED after %.1f s: %s"
                % (name, time.perf_counter() - t0, failed[name]))
        gc.collect()    # a phase's device buffers go before the next
    say("[cache] %d hit(s), %d miss(es) in %s — %s"
        % (cache.hits, cache.misses, cache_dir,
           "this run hit the cache" if cache.hits
           else "nothing was found there (a cold run)"))
    result = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    if args.phases != ",".join(PHASES):
        result["phases"] = phases       # a subset was asked for
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
