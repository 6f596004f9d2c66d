"""AOT compilation with cost/memory attribution from the artifact.

The decode engine's honesty discipline so far has been about TIME
(marginal decode timing, deep-timing trace spans); this module extends
it to WORK: what the compiled executables actually ask the hardware to
do.  Instead of letting ``jax.jit`` compile implicitly on first call,
:class:`AotFunction` routes every compilation through the ahead-of-time
path — ``jax.jit(f).lower(*args).compile()`` — and reads the compiler's
own accounting off the artifact the moment it exists:

- ``cost_analysis()``: FLOPs and bytes-accessed of the optimized HLO —
  what XLA EMITTED after fusion, not hand math over the model config
  ("Operator Fusion in XLA", PAPERS.md: compiler-reported cost analyses
  are the ground truth for what fusion actually produced);
- ``memory_analysis()``: the executable's HBM reservation split into
  argument / output / alias (donated) / temp / generated-code bytes —
  the number a capacity planner needs, read from the artifact instead
  of estimated.

Call dispatch stays cheap: the cache key is derived from ONE
distinguishing argument's shape/dtype (declared per call site via
``key_fn`` — the weights and cache shapes are session-fixed, so the
varying argument alone identifies the executable), and the compiled
``jax.stages.Compiled`` object's call path is as fast as the jit
dispatch it replaces (measured at parity on CPU).  Analysis runs ONCE
at compile time and is cached as a plain dict, so ``cost_report()`` is
a read, never a compile or a device sync.

Donation semantics, compile counting (``_cache_size()`` — the
observable behind the exactly-two-compiles contract), and greedy token
identity are all unchanged: the same traced function compiles to the
same executable, it just compiles through a path that hands back the
artifact's metadata.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

__all__ = ["AotFunction", "analyze_compiled", "kv_arg_bytes",
           "shape_key"]


def shape_key(arr) -> str:
    """The canonical executable-cache key for one distinguishing
    argument: ``"<shape joined by x>_<dtype>"`` (e.g. ``"1x512_int32"``
    for a batch-1 512-token prefill, ``"8_int32"`` for an 8-slot decode
    token vector).  Reads only metadata — no sync, no allocation beyond
    the string."""
    return "%s_%s" % ("x".join(str(d) for d in arr.shape) or "scalar",
                      arr.dtype.name)


def kv_arg_bytes(cache) -> int:
    """Device bytes of the K/V payload (plus riding quantization
    scales) in a decode-cache pytree — the executable's cache-argument
    footprint, summed from the aval metadata of the arrays the
    executable was compiled for.  Excludes the index vector and the
    paged block table: those are bookkeeping, not cache payload, so
    this is the figure that reconciles with
    ``inference.kv_reachable_bytes`` accounting (pinned by tests)."""
    total = 0
    for c in cache:
        # "state" is the recurrent layout's whole payload (jit.cache):
        # positional caches have no such field, so the transformer
        # figures are unchanged
        for field in ("k", "v", "k_scale", "v_scale", "state", "norm",
                      "latent"):
            a = getattr(c, field, None)
            if a is not None:
                total += int(a.size) * a.dtype.itemsize
    return total


def analyze_compiled(compiled) -> dict:
    """One executable's cost/memory attribution as a JSON-safe dict.

    Read from the compiled artifact (``cost_analysis`` /
    ``memory_analysis``); a backend that cannot answer (some plugin
    runtimes) degrades to an explicit ``*_unavailable`` marker instead
    of fake zeros, so a report can never present a missing analysis as
    a measured one."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if "flops" in ca and "bytes accessed" in ca:
            out["flops"] = float(ca["flops"])
            out["bytes_accessed"] = float(ca["bytes accessed"])
        else:
            # a partial answer gets the explicit marker, never a fake
            # 0.0 a later regression diff would flag as real movement
            out["cost_analysis_unavailable"] = (
                "backend cost_analysis() lacks flops/bytes-accessed "
                "(keys: %s)" % sorted(ca)[:8])
    except Exception as e:  # noqa: BLE001 - backend-dependent API
        out["cost_analysis_unavailable"] = str(e)[:200]
    try:
        ma = compiled.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        outb = int(ma.output_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        code = int(ma.generated_code_size_in_bytes)
        out.update(argument_bytes=arg, output_bytes=outb,
                   alias_bytes=alias, temp_bytes=temp,
                   generated_code_bytes=code,
                   # aliased (donated) bytes appear in BOTH the
                   # argument and output totals but occupy one buffer
                   hbm_reserved_bytes=arg + outb - alias + temp + code)
    except Exception as e:  # noqa: BLE001 - backend-dependent API
        out["memory_analysis_unavailable"] = str(e)[:200]
    return out


class AotFunction:
    """A ``jax.jit``-wrapped function whose executables are compiled
    ahead-of-time and whose cost/memory attribution is part of the
    artifact.

    ``key_fn(*args) -> str`` names the executable one call shape maps
    to (usually :func:`shape_key` of the single argument whose shape
    varies); ``meta_fn(*args) -> dict``, when given, runs once at
    compile time and its result rides the cost entry (the decode steps
    attach their cache argument's ``kv_cache_bytes`` this way).

    Not a tracing cache: two shapes that key equal MUST lower to the
    same executable — key functions are declared next to the call
    site's shape contract, where review can check that.
    """

    __slots__ = ("_jitted", "_key_fn", "_meta_fn", "name", "_exes",
                 "_costs")

    def __init__(self, jitted, key_fn: Callable[..., str],
                 name: str = "", meta_fn: Optional[Callable] = None):
        self._jitted = jitted
        self._key_fn = key_fn
        self._meta_fn = meta_fn
        self.name = name
        self._exes: Dict[str, object] = {}
        self._costs: Dict[str, dict] = {}

    def __call__(self, *args):
        key = self._key_fn(*args)
        exe = self._exes.get(key)
        if exe is None:
            exe = self._compile_miss(key, args)
        return exe(*args)

    def _compile_miss(self, key: str, args):
        """The cold path: AOT lower+compile, then read the artifact's
        attribution once and cache it beside the executable.  Runs
        exactly once per key — never on the steady-state tick."""
        exe = self._jitted.lower(*args).compile()
        entry = analyze_compiled(exe)
        entry["key"] = key
        if self._meta_fn is not None:
            entry.update(self._meta_fn(*args))
        self._costs[key] = entry
        self._exes[key] = exe
        return exe

    # the observable behind the exactly-two-compiles contract: one
    # entry per XLA compilation, same counting jax.jit's cache gave
    def _cache_size(self) -> int:
        return len(self._exes)

    @property
    def compiles(self) -> int:
        """Lifetime compilation count (entries are never evicted)."""
        return len(self._exes)

    def cost_report(self) -> Dict[str, dict]:
        """{key: attribution entry} for every compiled executable —
        copies of the compile-time analysis, so reporting never
        touches XLA or the device."""
        return {k: dict(v) for k, v in self._costs.items()}

    def last_cost(self) -> Optional[dict]:
        """The most recently compiled executable's entry (None before
        the first compile) — the steady-state executable for
        fixed-shape call sites like the pool decode step."""
        if not self._costs:
            return None
        return dict(self._costs[next(reversed(self._costs))])
