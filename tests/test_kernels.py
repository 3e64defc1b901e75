"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes and
dtypes, plus hypothesis property sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st
from _wkv6_decay import wkv6_decay

from repro.kernels.lora_dual import lora_dual, lora_dual_ref
from repro.kernels.swa_attention import swa_attention, swa_attention_ref
from repro.kernels.wkv6_scan import wkv6_scan, wkv6_scan_ref


# ---------------------------------------------------------------------------
# lora_dual
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-3), (jnp.bfloat16, 1e-1)])
@pytest.mark.parametrize("M,K,N,r", [(128, 128, 128, 1), (200, 300, 250, 4),
                                     (64, 512, 128, 16), (256, 128, 384, 8)])
def test_lora_dual_allclose(M, K, N, r, dtype, atol):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    mk = lambda k, s, sc=0.05: (jax.random.normal(k, s) * sc).astype(dtype)
    x, xd = mk(ks[0], (M, K), 1.0), mk(ks[1], (M, K), 1.0)
    w = mk(ks[2], (K, N))
    a, ad = mk(ks[3], (K, r)), mk(ks[4], (K, r))
    b, bd = mk(ks[5], (r, N)), mk(ks[6], (r, N))
    y, yd = lora_dual(x, xd, w, a, ad, b, bd, scale=2.0, interpret=True)
    yr, ydr = lora_dual_ref(x, xd, w, a, ad, b, bd, 2.0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol, rtol=atol)
    np.testing.assert_allclose(np.asarray(yd, np.float32),
                               np.asarray(ydr, np.float32), atol=atol, rtol=atol)


def test_lora_dual_matches_jax_jvp():
    """The kernel's (y, ydot) must equal jax.jvp of the LoRA projection —
    the semantics SPRY's forward gradients rely on."""
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    M, K, N, r = 64, 96, 80, 2
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (M, K))
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (r, N)) * 0.05

    def f(x_, a_, b_):
        return x_ @ w + 2.0 * (x_ @ a_) @ b_

    y_ref, yd_ref = jax.jvp(f, (x, a, b), (xd, ad, bd))
    y, yd = lora_dual(x, xd, w, a, ad, b, bd, scale=2.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yd_ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(M=st.integers(1, 4), K=st.integers(1, 4), N=st.integers(1, 4),
       r=st.integers(1, 4))
def test_lora_dual_odd_shapes(M, K, N, r):
    """Padding path: arbitrary small shapes (not block multiples)."""
    M, K, N = M * 37, K * 53, N * 41
    ks = jax.random.split(jax.random.PRNGKey(M * K * N), 7)
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (M, K))
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (r, N)) * 0.05
    y, yd = lora_dual(x, xd, w, a, ad, b, bd, scale=1.0, block_m=64,
                      block_n=64, block_k=64, interpret=True)
    yr, ydr = lora_dual_ref(x, xd, w, a, ad, b, bd, 1.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(ydr), atol=1e-3,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd,W,bq,bk", [
    (2, 4, 4, 256, 64, None, 64, 64),
    (1, 4, 2, 256, 64, 96, 64, 64),
    (2, 2, 2, 512, 32, 128, 128, 128),
    (1, 8, 4, 128, 64, 32, 32, 32),
    (1, 2, 2, 512, 64, 200, 64, 128),
    (1, 1, 1, 1024, 64, 256, 128, 64),
])
def test_swa_attention_allclose(B, H, KV, S, hd, W, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    out = swa_attention(q, k, v, window=W, block_q=bq, block_k=bk,
                        interpret=True)
    kr = jnp.repeat(k, H // KV, axis=1)
    vr = jnp.repeat(v, H // KV, axis=1)
    ref = swa_attention_ref(q, kr, vr, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)


def test_swa_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, H, S, hd = 1, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, S, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, hd)).astype(jnp.bfloat16)
    out = swa_attention(q, k, v, window=64, block_q=64, block_k=64,
                        interpret=True)
    ref = swa_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(wmul=st.integers(1, 6))
def test_swa_attention_window_sweep(wmul):
    W = wmul * 48
    ks = jax.random.split(jax.random.PRNGKey(wmul), 3)
    q = jax.random.normal(ks[0], (1, 2, 384, 32))
    k = jax.random.normal(ks[1], (1, 2, 384, 32))
    v = jax.random.normal(ks[2], (1, 2, 384, 32))
    out = swa_attention(q, k, v, window=W, block_q=96, block_k=96,
                        interpret=True)
    ref = swa_attention_ref(q, k, v, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)


# ---------------------------------------------------------------------------
# wkv6_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,bs,decay", [
    pytest.param(2, 128, 4, 32, 32, "sigmoid", id="2-128-4-32-32"),
    pytest.param(1, 100, 2, 64, 64, "sigmoid", id="1-100-2-64-64"),
    pytest.param(2, 64, 8, 16, 16, "sigmoid", id="2-64-8-16-16"),
    pytest.param(1, 256, 1, 8, 128, "sigmoid", id="1-256-1-8-128"),
    pytest.param(2, 96, 2, 16, 32, "strong", id="strong-2-96-2-16-32"),
    pytest.param(1, 75, 2, 64, 64, "strong", id="strong-1-75-2-64-64"),
    pytest.param(2, 96, 2, 16, 32, "near1", id="near1-2-96-2-16-32"),
    pytest.param(2, 10, 2, 16, 64, "sigmoid", id="2-10-2-16-64"),
])
def test_wkv6_scan_allclose(B, S, H, hd, bs, decay):
    """S under one sub-chunk (10), not a multiple of one (75, 100) and
    across many (the state carry); w down to 1e-30 and exactly 0, and w at
    1 - 1e-6."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    w = wkv6_decay(decay, ks[3], (B, S, H, hd))
    u = jax.random.normal(ks[4], (H, hd)) * 0.5
    y = wkv6_scan(r, k, v, w, u, block_s=bs, interpret=True)
    yr, _ = wkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)


def test_wkv6_matches_model_recurrence():
    """Kernel semantics == the model's decode recurrence state evolution."""
    from repro.models.ssm import wkv6_recurrence
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    B, S, H, hd = 1, 32, 2, 16
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) * 0.3 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd)))
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    y_kernel = wkv6_scan(r, k, v, w, u, block_s=16, interpret=True)
    y_model, _ = wkv6_recurrence(r, k, v, w, u,
                                 jnp.zeros((B, H, hd, hd), jnp.float32))
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=1e-5, rtol=1e-5)
