"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.cannon_mm import blocked_matmul, matmul_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.ssd_scan import ssd_decode_step, ssd_ref, ssd_scan

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("mkn,blocks", [
    ((256, 256, 256), (128, 128, 128)),
    ((512, 256, 384), (256, 128, 128)),
    ((128, 512, 128), (128, 128, 256)),
    ((128, 128, 128), (128, 128, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cannon_mm(mkn, blocks, dtype):
    M, K, N = mkn
    bm, bn, bk = blocks
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (M, K), dtype)
    b = jax.random.normal(k2, (K, N), dtype)
    out = blocked_matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                         interpret=True)
    ref = matmul_ref(a, b)
    err = np.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    scale = max(1.0, float(np.abs(np.asarray(ref, np.float32)).max()))
    assert err / scale < TOL[dtype], err


@pytest.mark.parametrize("shape", [
    # (B, Hq, Hkv, Sq, Skv, D, q_offset)
    (2, 4, 2, 256, 256, 64, 0),
    (1, 8, 8, 128, 512, 32, 384),
    (2, 4, 1, 128, 128, 128, 0),
    (1, 2, 2, 384, 384, 64, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(shape, dtype, causal):
    B, Hq, Hkv, Sq, Skv, D, off = shape
    if not causal and off:
        pytest.skip("offset only meaningful with causal")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, q_offset=off,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, q_offset=off)
    err = np.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    assert err < TOL[dtype], err


@pytest.mark.parametrize("dims", [
    # (B, S, H, P, G, N, chunk)
    (2, 256, 8, 16, 2, 32, 64),
    (1, 128, 4, 32, 1, 16, 128),
    (2, 128, 6, 8, 3, 8, 32),
    (1, 64, 2, 64, 2, 64, 16),
])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_ssd_scan(dims, backend):
    B, S, H, P, G, N, L = dims
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y, s = ssd_scan(x, dt, A, Bm, Cm, chunk=L, backend=backend,
                    interpret=backend == "pallas")
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-3, atol=1e-4)


def test_ssd_init_state_and_decode_chain():
    """Chunked scan with an initial state == decode recurrence continuation."""
    B, S, H, P, G, N = 1, 32, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    # full scan
    y_full, s_full = ssd_scan(x, dt, A, Bm, Cm, chunk=8)
    # first half scan, then second half with carried state
    y1, s1 = ssd_scan(x[:, :16], dt[:, :16], A, Bm[:, :16], Cm[:, :16],
                      chunk=8)
    y2, s2 = ssd_scan(x[:, 16:], dt[:, 16:], A, Bm[:, 16:], Cm[:, 16:],
                      init_state=s1, chunk=8)
    np.testing.assert_allclose(np.asarray(y_full[:, 16:]), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                               rtol=1e-4, atol=1e-5)
    # decode steps continue exactly
    st = s2
    yd, st = ssd_decode_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], st)
    assert np.isfinite(np.asarray(yd)).all()
