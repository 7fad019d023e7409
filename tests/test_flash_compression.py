"""Flash-attention Pallas kernel vs oracle; int8 gradient compression."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist.compression import (
    compress_roundtrip_error,
    dequantize_int8,
    quantize_int8,
)
from repro.kernels.flashattn import flash_attention, flash_attention_bshd
from repro.models.attention import blockwise_attention


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,bq,bk", [(128, 64, 64), (256, 128, 64)])
def test_flash_matches_blockwise(causal, s, bq, bk):
    rng = np.random.RandomState(0)
    b, h, dh = 2, 2, 64
    q = jnp.asarray(rng.randn(b, s, h, dh).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, h, dh).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, h, dh).astype(np.float32))
    got = np.asarray(flash_attention_bshd(q, k, v, causal=causal,
                                          interpret=True, bq=bq, bk=bk))
    want = np.asarray(blockwise_attention(q, k, v, causal=causal, q_block=64))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    assert o.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(o.astype(jnp.float32))))


def test_flash_extreme_logits_stable():
    """online softmax must survive large score magnitudes."""
    q = jnp.full((1, 128, 64), 8.0, jnp.float32)
    k = jnp.full((1, 128, 64), 8.0, jnp.float32)
    v = jnp.ones((1, 128, 64), jnp.float32)
    o = flash_attention(q, k, v, causal=False, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o), 1.0, rtol=1e-5)


# ------------------------------ compression ------------------------------- #


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(1, 10_000),
       scale=st.floats(1e-6, 1e4))
def test_int8_roundtrip_bounded_error(seed, n, scale):
    rng = np.random.RandomState(seed)
    g = jnp.asarray((rng.randn(n) * scale).astype(np.float32))
    q, s = quantize_int8(g)
    back = dequantize_int8(q, s, g.shape)
    # per-block max-abs scaling → elementwise error ≤ scale/127 ≤ max/127
    err = np.abs(np.asarray(back) - np.asarray(g))
    bound = np.max(np.abs(np.asarray(g))) / 127.0 + 1e-12
    assert err.max() <= bound * 1.01


def test_int8_zero_grad_exact():
    g = jnp.zeros(100)
    q, s = quantize_int8(g)
    assert np.all(np.asarray(dequantize_int8(q, s, g.shape)) == 0)


def test_roundtrip_error_metric_small():
    rng = np.random.RandomState(3)
    g = jnp.asarray(rng.randn(4, 1000).astype(np.float32))
    rel = float(compress_roundtrip_error(g))
    assert 0.0 < rel < 0.01          # int8 ≈ 1/127 per-block relative error
    assert float(compress_roundtrip_error(jnp.zeros(64))) == 0.0


def test_error_feedback_reduces_bias():
    """with feedback, the *accumulated* quantization error stays bounded
    instead of growing linearly (the 1-bit-Adam argument)."""
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(4096).astype(np.float32)) * 1e-3
    err = jnp.zeros_like(g)
    acc_fb = jnp.zeros_like(g)      # sum of dequantized (with feedback)
    acc_nofb = jnp.zeros_like(g)
    for _ in range(20):
        q, s = quantize_int8(g + err)
        deq = dequantize_int8(q, s, g.shape)
        err = (g + err) - deq
        acc_fb = acc_fb + deq
        q2, s2 = quantize_int8(g)
        acc_nofb = acc_nofb + dequantize_int8(q2, s2, g.shape)
    true = np.asarray(g) * 20
    err_fb = np.linalg.norm(np.asarray(acc_fb) - true)
    err_nofb = np.linalg.norm(np.asarray(acc_nofb) - true)
    assert err_fb <= err_nofb * 1.05
    assert err_fb < np.linalg.norm(true) * 0.05
