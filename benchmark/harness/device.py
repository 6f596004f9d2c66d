"""The chip: the look for it, its published peaks, the compile cache, its
memory, and the operations and bytes of the work, computed from shapes."""
from __future__ import annotations

import os
import sys

# One row per device kind, with its source; a kind that is not here is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class NoChip(SystemExit):
    """Raised (exit code 2, no result line) when the chips are not there."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError("device kind %r is not in benchmark/harness/device.py"
                       " PEAKS; add its row with a source before computing "
                       "any utilisation" % (kind,))
    return PEAKS[kind]


def require_chips(jax, chips: int, platform: str = "tpu") -> dict:
    """The first ``chips`` devices, or exit 2 without a result line.  Never
    a fall-back to the CPU."""
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        print("benchmark: needs %d %s chip(s), found %d device(s) of "
              "platform %r (JAX_PLATFORMS=%r); refusing to run"
              % (chips, platform.upper(), len(devs), devs[0].platform,
                 os.environ.get("JAX_PLATFORMS")), file=sys.stderr)
        raise NoChip(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def use_compile_cache(jax, root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself), else
    the fixed ``<checkout>/.jax_cache``: the path is part of the key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Persistent-cache hits and misses of this process, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory(jax, chips: int) -> list:
    """Per device, as the backend reports: bytes in use, their peak, and the
    peak of what it reserved besides.  The TPU runtime keeps a running
    program's temporaries in a reservation of their own: BERT-base's train
    step holds 1.6 GB of buffers and reserves 13.4 GB, which only
    ``peak_bytes_reserved`` shows (my chip run, PR 25)."""
    out = []
    for d in jax.devices()[:chips]:
        ms = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "peak_bytes_reserved": ms.get("peak_bytes_reserved")})
    return out


def memory_peak_bytes(mem: list):
    """The fullest chip's peak: buffers in use plus the reservation for
    programs' temporaries."""
    peaks_ = [m["peak_bytes_in_use"] + (m["peak_bytes_reserved"] or 0)
              for m in mem if m["peak_bytes_in_use"] is not None]
    return max(peaks_) if peaks_ else None


def storage_census(jax, stated: dict) -> dict:
    """Bytes the process holds on the device, by element type, over every
    live array of ``min_array_bytes`` or more, and how many of them are of
    a type the configuration does not state (``dtypes``).  The chip
    multiplies float32 operands as bfloat16, so what a served token shows
    of the stored precision is within the noise of the seeds (PERF.md,
    Findings); what is stored is therefore read, not inferred."""
    by_type = {}
    for a in jax.live_arrays():
        if a.nbytes >= stated["min_array_bytes"]:
            by_type[str(a.dtype)] = by_type.get(str(a.dtype), 0) + a.nbytes
    return {"by_type": by_type,
            "unstated_bytes": sum(n for t, n in by_type.items()
                                  if t not in stated["dtypes"])}


# -- operations and bytes from shapes ---------------------------------------

def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token requires (PaLM appendix B):
    6 x the matmul parameters, plus 12 x layers x width x sequence for the
    attention products.  Recomputed operations do not count.  Copied from
    ``TransformerLM.flops_per_token`` so that no PR can move it."""
    h, l = cfg["hidden_size"], cfg["num_layers"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    matmul_params = l * (4 * h * h + 2 * h * ff) + v * h
    return 6.0 * matmul_params + 12.0 * l * h * seq_len


def parameter_count(cfg: dict) -> int:
    h, l = cfg["hidden_size"], cfg["num_layers"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = 4 * h * h + 4 * h + 2 * h * ff + ff + h + 4 * h
    return l * per_layer + v * h + cfg["max_position"] * h + 2 * h


def decode_step_min_bytes(cfg: dict, live_positions: float,
                          weight_bytes: int, kv_bytes: int) -> float:
    """The least a batched decode step must read from HBM: every weight
    once (the tied head reads the whole embedding; the position table and
    the embedding rows gathered are left out as negligible) and the K and V
    of every live position of every layer."""
    h, l = cfg["hidden_size"], cfg["num_layers"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    weights = (l * (4 * h * h + 2 * h * ff + 9 * h + ff) + v * h + 2 * h)
    return weights * weight_bytes + 2.0 * l * h * kv_bytes * live_positions
