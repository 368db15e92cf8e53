"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from hypothesis_fallback import given, settings, st

from repro.kernels.decode_attn.ops import decode_attn
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.kernels.flashattn.ops import attention, block_for
from repro.kernels.flashattn.ref import attention_ref
from repro.kernels.mamba2_ssd.ops import ssd
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.pivot.ops import pivot, pivot_columns
from repro.kernels.pivot.ref import pivot_ref, unpivot_ref
from repro.kernels.rwkv6_scan.ops import wkv
from repro.kernels.rwkv6_scan.ref import wkv_ref


# -- pivot ------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 8), (256, 256), (300, 70), (1, 513)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_pivot_sweep(shape, dtype):
    x = jax.random.randint(jax.random.PRNGKey(0), shape, 0, 1 << 20
                           ).astype(dtype)
    np.testing.assert_array_equal(np.asarray(pivot(x, interpret=True)),
                                  np.asarray(x).T)


@given(st.integers(1, 70), st.integers(1, 70))
@settings(max_examples=15, deadline=None)
def test_pivot_property(n, w):
    x = jnp.arange(n * w, dtype=jnp.int32).reshape(n, w)
    np.testing.assert_array_equal(np.asarray(pivot(x, interpret=True)),
                                  np.asarray(x).T)


def test_pivot_columns_and_unpivot():
    rows = jax.random.randint(jax.random.PRNGKey(1), (100, 24), 0, 99,
                              dtype=jnp.int32)
    widths = [2, 4, 2, 16]
    cols = pivot_columns(rows, widths, interpret=True)
    refs = pivot_ref(rows, widths)
    for a, b in zip(cols, refs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(unpivot_ref(cols)),
                                  np.asarray(rows))


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,KV,hd,causal", [
    (2, 256, 256, 4, 2, 64, True),
    (1, 128, 384, 8, 8, 32, False),
    (2, 256, 256, 6, 2, 64, True),
    (1, 512, 512, 2, 1, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flashattn_sweep(B, Sq, Sk, H, KV, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * Sq + Sk), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd), dtype)
    got = attention(q, k, v, causal=causal, interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flashattn_block_size_invariance():
    """Blocks follow the sequence length (384 -> 128, 512 -> 512); causal
    rows over a prefix do not depend on what follows it."""
    assert (block_for(384), block_for(512)) == (128, 512)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 64))
    k = jax.random.normal(ks[1], (1, 512, 2, 64))
    v = jax.random.normal(ks[2], (1, 512, 2, 64))
    a = attention(q, k, v, interpret=True)[:, :384]
    b = attention(q[:, :384], k[:, :384], v[:, :384], interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


# -- decode attention ------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,length", [
    (2, 1024, 8, 2, 64, 700),
    (1, 512, 4, 4, 32, 512),
    (2, 2048, 8, 2, 64, 1),
    (1, 1024, 16, 2, 128, 1000),
])
def test_decode_attn_sweep(B, S, H, KV, hd, length):
    ks = jax.random.split(jax.random.PRNGKey(S + length), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kc = jax.random.normal(ks[1], (B, S, KV, hd))
    vc = jax.random.normal(ks[2], (B, S, KV, hd))
    got = decode_attn(q, kc, vc, length, interpret=True)
    want = decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- rwkv6 wkv -----------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 128, 2, 16, 32),
    (1, 256, 4, 32, 64),
    (1, 96, 1, 64, 32),
])
def test_rwkv6_wkv_sweep(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(7 + S), 6)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.9 + 0.05
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    st0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    y1, s1 = wkv(r, k, v, w, u, st0, interpret=True, chunk=chunk)
    y2, s2 = wkv_ref(r, k, v, w, u, st0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


# -- mamba2 ssd ------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,N,chunk", [
    (2, 128, 2, 16, 16, 32),
    (1, 256, 4, 32, 32, 64),
    (1, 64, 1, 64, 64, 64),
])
def test_mamba2_ssd_sweep(B, S, H, hd, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(9 + S), 6)
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    D = jnp.ones((H,))
    st0 = jax.random.normal(ks[5], (B, H, hd, N)) * 0.1
    y1, s1 = ssd(x, dt, A, Bm, Cm, D, st0, interpret=True, chunk=chunk)
    y2, s2 = ssd_ref(x, dt, A, Bm, Cm, D, st0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunk_invariance():
    """Chunk-parallel dual form must not depend on the chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    B, S, H, hd, N = 1, 128, 2, 16, 16
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    D = jnp.ones((H,))
    st0 = jnp.zeros((B, H, hd, N))
    y32, _ = ssd(x, dt, A, Bm, Cm, D, st0, interpret=True, chunk=32)
    y64, _ = ssd(x, dt, A, Bm, Cm, D, st0, interpret=True, chunk=64)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64),
                               rtol=2e-4, atol=2e-4)
