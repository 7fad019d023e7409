"""Chunked SSM algebra vs sequential recurrences (hypothesis sweeps)."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.ssm import _ssd_chunked, _wkv6_chunked


def wkv6_seq(r, k, v, logw, u):
    b, t, h, K = r.shape
    V = v.shape[-1]
    S = np.zeros((b, h, K, V))
    out = np.zeros((b, t, h, V))
    for i in range(t):
        kv = np.einsum("bhk,bhv->bhkv", k[:, i], v[:, i])
        out[:, i] = np.einsum("bhk,bhkv->bhv", r[:, i],
                              S + u[None, :, :, None] * kv)
        S = np.exp(logw[:, i])[..., None] * S + kv
    return out


def ssd_seq(x, B, C, loga):
    b, t, h, P = x.shape
    S = np.zeros((b, h, P, B.shape[-1]))
    out = np.zeros((b, t, h, P))
    for i in range(t):
        a = np.exp(loga[:, i])
        S = a[..., None, None] * S + np.einsum("bhp,bn->bhpn", x[:, i], B[:, i])
        out[:, i] = np.einsum("bhpn,bn->bhp", S, C[:, i])
    return out


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), t=st.sampled_from([8, 16, 32, 48]),
       chunk=st.sampled_from([4, 8, 16]))
def test_wkv6_chunked_equals_sequential(seed, t, chunk):
    if t % chunk:
        t = (t // chunk) * chunk or chunk
    rng = np.random.RandomState(seed)
    b, h, K, V = 2, 3, 8, 8
    r = rng.randn(b, t, h, K).astype(np.float32)
    k = rng.randn(b, t, h, K).astype(np.float32)
    v = rng.randn(b, t, h, V).astype(np.float32)
    logw = -np.exp(rng.randn(b, t, h, K).astype(np.float32))
    u = rng.randn(h, K).astype(np.float32)
    got = np.asarray(_wkv6_chunked(*map(jnp.asarray, (r, k, v, logw)),
                                   jnp.asarray(u), chunk))
    want = wkv6_seq(r, k, v, logw, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), t=st.sampled_from([8, 16, 32]),
       chunk=st.sampled_from([4, 8]))
def test_ssd_chunked_equals_sequential(seed, t, chunk):
    rng = np.random.RandomState(seed)
    b, h, P, N = 2, 3, 4, 5
    x = rng.randn(b, t, h, P).astype(np.float32)
    B = rng.randn(b, t, N).astype(np.float32)
    C = rng.randn(b, t, N).astype(np.float32)
    loga = -np.abs(rng.randn(b, t, h).astype(np.float32))
    got = np.asarray(_ssd_chunked(*map(jnp.asarray, (x, B, C, loga)), chunk))
    want = ssd_seq(x, B, C, loga)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_wkv6_extreme_decay_stable():
    """No overflow with near-zero decay (exp(-exp(x)) can be tiny)."""
    b, t, h, K, V = 1, 32, 1, 4, 4
    rng = np.random.RandomState(0)
    r = rng.randn(b, t, h, K).astype(np.float32)
    k = rng.randn(b, t, h, K).astype(np.float32)
    v = rng.randn(b, t, h, V).astype(np.float32)
    logw = np.full((b, t, h, K), -80.0, np.float32)    # decay ≈ 0
    u = np.zeros((h, K), np.float32)
    got = np.asarray(_wkv6_chunked(*map(jnp.asarray, (r, k, v, logw)),
                                   jnp.asarray(u), 8))
    assert np.all(np.isfinite(got))
