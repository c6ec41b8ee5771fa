"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd,K,T,causal,window", [
    (2, 128, 4, 64, 2, 128, True, 0),      # GQA causal
    (1, 256, 4, 64, 1, 256, True, 64),     # MQA sliding window
    (2, 128, 4, 64, 4, 256, True, 0),      # decode-ish: T > S
    (1, 128, 2, 32, 2, 128, False, 0),     # encoder (bidirectional)
    (1, 200, 4, 64, 1, 200, True, 64),     # ragged: padded to whole blocks
    (1, 100, 2, 32, 2, 100, False, 0),     # ragged encoder: padded keys masked
    pytest.param(1, 512, 8, 128, 2, 512, True, 128,    # bigger window
                 marks=pytest.mark.slow),
])
def test_flash_attention(dtype, B, S, H, hd, K, T, causal, window):
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, T, K, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, T, K, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=64, bk=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,s,h,p,g,n,L", [
    (2, 128, 4, 32, 1, 32, 32),
    (1, 256, 2, 64, 1, 64, 64),
    (1, 64, 4, 16, 2, 16, 16),             # 2 B/C groups
    pytest.param(1, 256, 8, 64, 1, 128, 128,   # production-like state size
                 marks=pytest.mark.slow),
])
def test_ssd_scan_kernel(b, s, h, p, g, n, L):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, g, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, g, n)), jnp.float32)
    out = ssd_scan(x, dt, A, B, C, L, interpret=True)
    want = ref.ssd_scan_ref(x, dt, A, B, C, L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.slow
def test_ssd_chunked_equals_sequential():
    """The chunked SSD algorithm == the O(S) state recurrence definition."""
    b, s, h, p, g, n = 2, 128, 4, 32, 1, 32
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, g, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, g, n)), jnp.float32)
    for chunk in (16, 32, 64, 128):
        got = ref.ssd_scan_ref(x, dt, A, B, C, chunk)
        want = ref.ssd_scan_naive(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,S,W,bs,bw", [
    pytest.param(2, 128, 512, 64, 128, marks=pytest.mark.slow),
    pytest.param(1, 256, 256, 128, 256, marks=pytest.mark.slow),
    (3, 64, 128, 64, 128),
    (2, 200, 128, 64, 128),                # ragged: padded to whole blocks
    pytest.param(1, 512, 1024, 128, 512, marks=pytest.mark.slow),
])
def test_rglru_scan_kernel(B, S, W, bs, bw):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (B, S, W)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((B, S, W)), jnp.float32)
    out = rglru_scan(a, b, block_seq=bs, block_w=bw, interpret=True)
    want = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_rglru_scan_matches_python_loop():
    B, S, W = 1, 37, 8
    a = np.asarray(RNG.uniform(0.5, 0.999, (B, S, W)), np.float32)
    b = np.asarray(RNG.standard_normal((B, S, W)), np.float32)
    h = np.zeros((B, W), np.float32)
    want = np.zeros_like(a)
    for t in range(S):
        h = a[:, t] * h + b[:, t]
        want[:, t] = h
    got = ref.rglru_scan_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


def test_ops_wrappers_jit():
    from repro.kernels import ops
    q = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, k, causal=True)
    assert out.shape == q.shape
