"""Fused-op tests (pallas kernels + their gates/fallbacks).

The pallas kernel itself needs a real TPU; CPU CI exercises the gate and the
XLA fallback, and ``chip_smoke.py --phases kernels`` runs the kernel on
the chip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.ops import flash_attention, flash_attention_supported
from paddle_tpu.ops.flash_attention import (
    FLASH_MIN_SEQ,
    detect_causal_additive_mask,
)


def test_gate_rejects_cpu_and_odd_shapes():
    if jax.default_backend() != "tpu":
        assert not flash_attention_supported((2, 4, 8192, 64), jnp.bfloat16)
    else:  # pragma: no cover - hardware only
        assert flash_attention_supported((2, 4, FLASH_MIN_SEQ, 64), jnp.bfloat16)
        assert not flash_attention_supported((2, 4, FLASH_MIN_SEQ - 128, 64), jnp.bfloat16)
        assert not flash_attention_supported((2, 4, FLASH_MIN_SEQ, 96), jnp.bfloat16)
        assert not flash_attention_supported((2, 4, FLASH_MIN_SEQ, 64), jnp.float16)
        assert not flash_attention_supported((2, 4, FLASH_MIN_SEQ, 64), jnp.bfloat16, dropout_p=0.1)


def test_fallback_matches_manual_softmax(rng):
    B, H, L, D = 2, 3, 16, 8
    q = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    out = flash_attention(q, k, v, causal=True)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((L, L), bool))
    s = np.where(mask, s, np.finfo(np.float32).min)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    expect = np.einsum("bhqk,bhkd->bhqd", w, v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_detect_causal_additive_mask():
    L = 8
    idx = np.arange(L)
    allow = idx[None, :] <= idx[:, None]
    causal = np.where(allow, 0.0, np.finfo(np.float32).min).astype(np.float32)
    assert detect_causal_additive_mask(jnp.asarray(causal))
    assert detect_causal_additive_mask(jnp.asarray(causal), seq_len=L)
    assert not detect_causal_additive_mask(jnp.asarray(causal), seq_len=2 * L)
    assert not detect_causal_additive_mask(None)
    assert not detect_causal_additive_mask(jnp.zeros((L, L)))  # no -inf band
    assert not detect_causal_additive_mask(jnp.zeros((1, 1)))  # vacuous 1x1
    assert not detect_causal_additive_mask(jnp.asarray(causal)[None])  # 3-D
    padded = causal.copy()
    padded[0, 0] = -1.0  # not a pure causal pattern
    assert not detect_causal_additive_mask(jnp.asarray(padded))


def test_sdpa_routes_and_matches(rng):
    """scaled_dot_product_attention equals the naive path everywhere CI runs."""
    B, H, L, D = 2, 2, 32, 8
    q = pt.to_tensor(rng.randn(B, H, L, D).astype(np.float32))
    out = pt.nn.functional.scaled_dot_product_attention(q, q, q, is_causal=True)
    out2 = flash_attention(q.value, q.value, q.value, causal=True)
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(out2),
                               rtol=1e-5, atol=1e-6)
