"""Forward parity of the Pallas WKV6 kernel against the pure-jnp chunked
oracle (``models/rwkv6.wkv_chunked``), in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _inputs(key, B, T, H, D):
    ks = jax.random.split(jax.random.key(key), 5)
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D))
    # the model's decay parameterisation: dec clamped <= 0, w = exp(-exp(dec))
    # lands w in (0, 1] — exactly what the kernel's log-space carry assumes
    dec = jnp.clip(jax.random.normal(ks[3], (B, T, H, D)), None, 0.0)
    w = jnp.exp(-jnp.exp(dec))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    return r, k, v, w, u


@pytest.mark.parametrize("B,T,H,D,chunk", [
    (2, 128, 2, 16, 64),     # multi-chunk, multi-batch
    (1, 64, 4, 8, 64),       # single chunk exactly
    (2, 256, 2, 32, 32),     # many small chunks
])
def test_wkv6_fwd_parity(B, T, H, D, chunk):
    from repro.kernels.wkv6 import wkv6_chunked
    from repro.models.rwkv6 import wkv_chunked
    r, k, v, w, u = _inputs(B * 1000 + T, B, T, H, D)
    got = wkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    want, _ = wkv_chunked(r, k, v, w, u)
    assert got.shape == want.shape == (B, T, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-3)


def test_wkv6_fwd_parity_under_jit():
    """The kernel must trace cleanly inside jit with the oracle's exact
    input distribution (the serve/train paths always call it jitted)."""
    from repro.kernels.wkv6 import wkv6_chunked
    from repro.models.rwkv6 import wkv_chunked
    B, T, H, D = 1, 128, 2, 16
    r, k, v, w, u = _inputs(7, B, T, H, D)
    got = jax.jit(lambda *a: wkv6_chunked(*a, chunk=64, interpret=True))(r, k, v, w, u)
    want, _ = wkv_chunked(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-3)
