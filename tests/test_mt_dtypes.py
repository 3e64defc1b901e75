"""bf16/fp16 operand coverage through the _mt and _jvps kernel variants
(ISSUE 4 satellite).

The kernels accumulate in fp32 regardless of operand dtype
(``preferred_element_type=jnp.float32`` on every dot; fp32 VMEM scratch):

- lora_dual_mt: the output is the fp32 accumulation rounded ONCE to the
  operand dtype — asserted BITWISE against the fp32-upcast oracle rounded
  the same way (the "fp32 accumulator" property).
- *_mt_jvps epilogues: jvp partials stay fp32 end-to-end — asserted against
  the fp32-upcast oracle at fp32-reduction tolerance (reduction order
  differs blockwise, so bitwise does not apply; the tolerance is the same
  ~1e-6 class the fp32 tests use).
- wkv6/mamba2 ops cast operands to fp32 at the layout step, so
  low-precision inputs follow the fp32 path exactly; swa keeps the operand
  dtype through the softmax-weights matmuls (p is rounded to v.dtype, as
  on real TPUs), so the oracle comparison uses per-dtype tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.lora_dual import (
    lora_dual_mt,
    lora_dual_mt_jvps,
    lora_dual_mt_jvps_ref,
    lora_dual_mt_ref,
)
from repro.kernels.swa_attention import (
    swa_attention_mt,
    swa_attention_mt_jvps,
    swa_attention_mt_jvps_ref,
    swa_attention_mt_ref,
)
from repro.kernels.wkv6_scan import (
    wkv6_scan_mt,
    wkv6_scan_mt_jvps,
    wkv6_scan_mt_jvps_ref,
    wkv6_scan_mt_ref,
)

TOL = {jnp.bfloat16: 2e-2, jnp.float16: 2e-3}
DTYPES = [jnp.bfloat16, jnp.float16]


def _f32(t):
    return jax.tree.map(lambda x: x.astype(jnp.float32), t)


def _lora_problem(dt, M=8, K=48, N=40, r=2, T=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (M, K)).astype(dt)
    w = (jax.random.normal(ks[1], (K, N)) * 0.05).astype(dt)
    a = jax.random.normal(ks[2], (K, r)) * 0.05      # fp32 master LoRA
    b = jax.random.normal(ks[3], (r, N)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    bd = jax.random.normal(ks[5], (T, r, N)) * 0.05
    xd = (jax.random.normal(ks[6], (T, M, K)) * 0.3).astype(dt)
    gy = jax.random.normal(ks[7], (M, N))
    return x, w, a, b, ad, bd, xd, gy


@pytest.mark.parametrize("dt", DTYPES)
def test_lora_mt_fp32_accumulator_bitwise(dt):
    """Low-precision operands, fp32 accumulation: the kernel output must be
    BITWISE the fp32-upcast oracle rounded once to the operand dtype —
    i.e. no intermediate rounding anywhere in the K-reduction."""
    x, w, a, b, ad, bd, xd, _ = _lora_problem(dt)
    y, yds = lora_dual_mt(x, xd, w, a, ad, b, bd, interpret=True)
    assert y.dtype == dt and yds.dtype == dt
    yr, ydr = lora_dual_mt_ref(x.astype(jnp.float32),
                               xd.astype(jnp.float32),
                               w.astype(jnp.float32), a, ad, b, bd, 1.0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr.astype(dt)))
    np.testing.assert_array_equal(np.asarray(yds),
                                  np.asarray(ydr.astype(dt)))


@pytest.mark.parametrize("dt", DTYPES)
def test_lora_jvps_fp32_out_vs_fp32_oracle(dt):
    """The epilogue's jvp partials stay fp32 for low-precision operands and
    match the fp32-upcast oracle at fp32-reduction tolerance."""
    x, w, a, b, ad, bd, xd, gy = _lora_problem(dt)
    jk = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, xdots=xd, impl="kernel",
                           interpret=True)
    assert jk.dtype == jnp.float32
    jr = lora_dual_mt_jvps_ref(x.astype(jnp.float32),
                               w.astype(jnp.float32), a, ad, b, bd, gy,
                               1.0, xdots=xd.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jr), rtol=2e-5,
                               atol=1e-6)


def _wkv_problem(dt, B=2, S=64, H=2, hd=16, T=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 11)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)).astype(dt) * 0.3
               for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))).astype(dt)
    u = (jax.random.normal(ks[4], (H, hd)) * 0.3).astype(dt)
    rd, kd, vd = (jax.random.normal(ks[5 + i], (T, B, S, H, hd)).astype(dt)
                  * 0.3 for i in range(3))
    wd = (jax.random.normal(ks[8], (T, B, S, H, hd)) * 0.1).astype(dt)
    ud = (jax.random.normal(ks[9], (T, H, hd)) * 0.3).astype(dt)
    gy = jax.random.normal(ks[10], (B, S, H, hd))
    return (r, k, v, w, u), (rd, kd, vd, wd, ud), gy


@pytest.mark.parametrize("dt", DTYPES)
def test_wkv6_mt_low_precision_operands(dt):
    """wkv6 ops upcast to fp32 at the layout step — low-precision operands
    must match the oracle on the SAME upcast inputs bitwise-rounded-once:
    the state walk itself is pure fp32."""
    (r, k, v, w, u), (rd, kd, vd, wd, ud), _ = _wkv_problem(dt)
    y, yds = wkv6_scan_mt(r, k, v, w, u, rd, kd, vd, wd, ud, block_s=32,
                          interpret=True)
    assert y.dtype == jnp.float32
    yr, ydr = wkv6_scan_mt_ref(*_f32((r, k, v, w, u)),
                               *_f32((rd, kd, vd, wd, ud)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("dt", DTYPES)
def test_wkv6_jvps_low_precision_operands(dt):
    (r, k, v, w, u), (rd, kd, vd, wd, ud), gy = _wkv_problem(dt)
    jk = wkv6_scan_mt_jvps(r, k, v, w, u, rd, kd, vd, wd, gy, ud,
                           block_s=32, interpret=True)
    assert jk.dtype == jnp.float32
    jr = wkv6_scan_mt_jvps_ref(*_f32((r, k, v, w, u)),
                               *_f32((rd, kd, vd, wd)), gy,
                               ud.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jr), rtol=2e-5,
                               atol=1e-5)


def _swa_problem(dt, B=1, H=2, KV=2, S=64, hd=32, T=2, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (B, H, S, hd)).astype(dt)
    k = jax.random.normal(ks[1], (B, KV, S, hd)).astype(dt)
    v = jax.random.normal(ks[2], (B, KV, S, hd)).astype(dt)
    qd = jax.random.normal(ks[3], (T, B, H, S, hd)).astype(dt)
    kd = jax.random.normal(ks[4], (T, B, KV, S, hd)).astype(dt)
    vd = jax.random.normal(ks[5], (T, B, KV, S, hd)).astype(dt)
    gy = jax.random.normal(ks[6], (B, H, S, hd))
    return (q, k, v), (qd, kd, vd), gy


@pytest.mark.parametrize("dt", DTYPES)
def test_swa_mt_low_precision_operands(dt):
    """swa keeps the operand dtype through the softmax-weights matmul (p is
    rounded to v.dtype, as on real TPUs) — per-dtype tolerance vs the
    fp32-upcast oracle."""
    (q, k, v), (qd, kd, vd), _ = _swa_problem(dt)
    out, outds = swa_attention_mt(q, k, v, qd, kd, vd, window=32,
                                  block_q=32, block_k=32, interpret=True)
    assert out.dtype == dt
    outr, outdr = swa_attention_mt_ref(*_f32((q, k, v)),
                                       *_f32((qd, kd, vd)), window=32)
    tol = TOL[dt]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(outr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(outds, np.float32),
                               np.asarray(outdr), atol=tol, rtol=tol)


@pytest.mark.parametrize("dt", DTYPES)
def test_swa_jvps_low_precision_operands(dt):
    (q, k, v), (qd, kd, vd), gy = _swa_problem(dt)
    jk = swa_attention_mt_jvps(q, k, v, qd, kd, vd, gy, window=32,
                               block_q=32, block_k=32, interpret=True)
    assert jk.dtype == jnp.float32
    jr = swa_attention_mt_jvps_ref(*_f32((q, k, v)), *_f32((qd, kd, vd)),
                                   gy, window=32)
    tol = TOL[dt]
    denom = float(jnp.abs(jr).max())
    np.testing.assert_allclose(np.asarray(jk) / denom,
                               np.asarray(jr) / denom, atol=tol, rtol=tol)
