"""``paddle_tpu.ops`` — fused TPU kernels (pallas).

Reference parity: the reference's hand-fused CUDA ops —
``operators/fused/fused_attention_op.cu``, ``fused_gate_attention_op`` and the
``incubate.nn.FusedMultiHeadAttention`` surface.  Here the hot ops are pallas
TPU kernels (SURVEY §7 MFU target): flash attention keeps the [L, L] score
matrix out of HBM entirely, which is the bandwidth win that decides MFU at
long sequence length.
"""
from .flash_attention import (  # noqa: F401
    DECODE_ROUTES,
    decode_attention,
    decode_attention_supported,
    decode_route,
    dequantize_kv,
    flash_attention,
    flash_attention_supported,
    normalize_decode_route,
    paged_cache_write,
    paged_decode_attention,
    paged_decode_attention_supported,
    paged_kv_write,
    quantize_kv,
    reset_backend_memo,
)
from .pallas_decode import (  # noqa: F401
    decode_attention_kernel,
    paged_decode_attention_kernel,
)
from .power_retention import (  # noqa: F401
    power_retention_chunked,
    power_retention_quadratic,
    power_retention_step,
    symmetric_square,
)
from .selective_scan import (  # noqa: F401
    selective_scan_prefill,
    selective_scan_reference,
    selective_scan_step,
)

__all__ = ["flash_attention", "flash_attention_supported",
           "decode_attention", "decode_attention_supported",
           "paged_decode_attention", "paged_decode_attention_supported",
           "paged_cache_write", "paged_kv_write", "quantize_kv",
           "dequantize_kv",
           "decode_attention_kernel", "paged_decode_attention_kernel",
           "decode_route", "normalize_decode_route", "DECODE_ROUTES",
           "reset_backend_memo", "power_retention_chunked",
           "power_retention_step", "power_retention_quadratic",
           "symmetric_square", "selective_scan_prefill",
           "selective_scan_reference", "selective_scan_step"]
