"""Mamba2 multi-tangent kernel + dispatch (ISSUE 4 satellite — closes the
last ROADMAP mt-coverage gap).

Covers: the mamba2_scan kernels against the jnp scan oracle (which is
bit-identical to the scan previously inlined in models/ssm.py::mamba2_mix,
with the dt multiplication hoisted — an exact elementwise identity);
bitwise equality of stacked vs single-tangent passes; the dispatch routing
(vmap-of-tangents -> ONE multi-tangent pallas_call); the model-level
fresh-state fast path; and reverse-mode non-interference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forward_grad import forward_gradient
from repro.kernels import dispatch
from repro.kernels.mamba2_scan import (
    mamba2_scan,
    mamba2_scan_mt,
    mamba2_scan_mt_ref,
    mamba2_scan_mt_tangents,
    mamba2_scan_ref,
)


def _problem(B=2, S=96, H=3, hd=8, N=16, T=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    xdt = jax.random.normal(ks[0], (B, S, H, hd)) * 0.3
    bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
    cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    dec = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H)))
    xd = jax.random.normal(ks[4], (T, B, S, H, hd)) * 0.3
    bd = jax.random.normal(ks[5], (T, B, S, N)) * 0.3
    cd = jax.random.normal(ks[6], (T, B, S, N)) * 0.3
    dd = jax.random.normal(ks[7], (T, B, S, H)) * 0.1
    return (xdt, bm, cm, dec), (xd, bd, cd, dd)


@pytest.mark.parametrize("S", [96, 75])
def test_mamba2_primal_kernel_matches_ref(S):
    (xdt, bm, cm, dec), _ = _problem(S=S)
    y = mamba2_scan(xdt, bm, cm, dec, block_s=32, interpret=True)
    yr, _ = mamba2_scan_ref(xdt, bm, cm, dec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("S", [96, 75])
def test_mamba2_mt_matches_jvp_oracle(S):
    (xdt, bm, cm, dec), (xd, bd, cd, dd) = _problem(S=S)
    y, yds = mamba2_scan_mt(xdt, bm, cm, dec, xd, bd, cd, dd, block_s=32,
                            interpret=True)
    yr, ydr = mamba2_scan_mt_ref(xdt, bm, cm, dec, xd, bd, cd, dd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr), atol=1e-5,
                               rtol=1e-5)


def test_mamba2_mt_stacked_bitwise_equals_single_tangent_passes():
    (xdt, bm, cm, dec), (xd, bd, cd, dd) = _problem()
    T = xd.shape[0]
    yds = mamba2_scan_mt_tangents(xdt, bm, cm, dec, xd, bd, cd, dd,
                                  block_s=32, interpret=True)
    for t in range(T):
        one = mamba2_scan_mt_tangents(xdt, bm, cm, dec, xd[t:t + 1],
                                      bd[t:t + 1], cd[t:t + 1], dd[t:t + 1],
                                      block_s=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(yds[t]), np.asarray(one[0]))


def test_mamba2_mt_tangents_match_full_pass():
    (xdt, bm, cm, dec), (xd, bd, cd, dd) = _problem(seed=5)
    _, yds = mamba2_scan_mt(xdt, bm, cm, dec, xd, bd, cd, dd, block_s=32,
                            interpret=True)
    ydt = mamba2_scan_mt_tangents(xdt, bm, cm, dec, xd, bd, cd, dd,
                                  block_s=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(yds), np.asarray(ydt))


def test_mamba2_bc_streams_not_widened_per_head():
    """B_t/C_t are shared across heads — the kernel folds the head row back
    to its batch row in-grid, so the pallas_call's B/C operands must stay
    (B, S, N), never the (B*H, S, N) pre-broadcast."""
    B, S, H, hd, N = 1, 64, 4, 8, 16
    (xdt, bm, cm, dec), _ = _problem(B=B, S=S, H=H, hd=hd, N=N)
    jaxpr = jax.make_jaxpr(
        lambda *a: mamba2_scan(*a, block_s=32,
                               interpret=True))(xdt, bm, cm, dec)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    yield from walk(inner if hasattr(inner, "eqns")
                                    else inner.jaxpr)

    calls = list(walk(jaxpr.jaxpr))
    assert len(calls) == 1
    in_shapes = [tuple(v.aval.shape) for v in calls[0].invars]
    assert (B, S, N) in in_shapes, in_shapes
    assert (B * H, S, N) not in in_shapes, "B/C were widened per head"


# ---------------------------------------------------------------------------
# dispatch routing + estimator equivalence
# ---------------------------------------------------------------------------

def _pallas_calls(closed_jaxpr):
    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    yield from walk(inner if hasattr(inner, "eqns")
                                    else inner.jaxpr)
    return list(walk(closed_jaxpr.jaxpr))


def test_vmap_of_mamba2_tangents_traces_mt_route():
    """The batched estimator's vmap through ``dispatch.mamba2_mix`` must
    hit mamba2_scan_mt_tangents (leading-K tangent output), not a
    re-gridded T=1 kernel."""
    K = 4
    (xdt, bm, cm, dec), _ = _problem(B=1, S=32, H=2, hd=8, N=8, T=1)

    def f(prim):
        return jnp.mean(dispatch.mamba2_mix(prim["x"], prim["b"], prim["c"],
                                            prim["d"]) ** 2)

    prim = {"x": xdt, "b": bm, "c": cm, "d": dec}
    dispatch.set_backend("interpret")
    try:
        with dispatch.forward_ad_region():
            _, tangent_map = jax.linearize(f, prim)
        vs = jax.tree.map(lambda t: jnp.zeros((K,) + t.shape), prim)
        jaxpr = jax.make_jaxpr(jax.vmap(tangent_map))(vs)
    finally:
        dispatch.set_backend(None)

    calls = _pallas_calls(jaxpr)
    assert len(calls) == 1, f"expected ONE fused mt pallas_call, got {calls}"
    (out_aval,) = [v.aval for v in calls[0].outvars]
    assert out_aval.shape[0] == K, (
        f"tangent output {out_aval.shape} does not carry the leading K axis")


def test_mamba2_estimator_batched_jvps_bitwise_equal_sequential():
    """The batched K-tangent estimate through the dispatched mamba2 mixer
    must give jvps BITWISE equal to the sequential tangent_batch=1 run on
    the interpret backend — per-tangent kernel lanes are exact replicas of
    the T=1 pass."""
    B, S, H, hd, N = 1, 48, 2, 8, 8
    D = H * hd
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (B, S, D)) * 0.3
    w0 = jax.random.normal(ks[1], (D, D)) * 0.05
    bmw = jax.random.normal(ks[2], (D, N)) * 0.1
    cmw = jax.random.normal(ks[3], (D, N)) * 0.1
    peft = {"A": jax.random.normal(ks[4], (D, 2)) * 0.05,
            "B": jax.random.normal(ks[5], (2, D)) * 0.05}
    dec = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (B, S, H)))

    def loss(p):
        h = dispatch.lora_proj(x, w0, p["A"], p["B"], 2.0)
        y = dispatch.mamba2_mix(h.reshape(B, S, H, hd),
                                (h @ bmw).astype(jnp.float32),
                                (h @ cmw).astype(jnp.float32), dec)
        return jnp.mean(y * y)

    key = jax.random.PRNGKey(9)
    dispatch.set_backend("interpret")
    try:
        _, _, j_seq = forward_gradient(loss, peft, key, k_perturbations=4,
                                       tangent_batch=1)
        _, _, j_bat = forward_gradient(loss, peft, key, k_perturbations=4)
    finally:
        dispatch.set_backend(None)
    np.testing.assert_array_equal(np.asarray(j_seq), np.asarray(j_bat))


def test_mamba2_model_fast_path_matches_jnp_scan():
    """models/ssm.py::mamba2_mix under use_kernel_mixers() (fresh state)
    must produce the same output as the native scan path, and return
    state=None there (the estimator's loss closures never consume it)."""
    from repro.configs import get_config, reduce_config
    from repro.models.ssm import mamba2_mix, mamba2_params

    cfg = reduce_config(get_config("zamba2-1.2b"))
    key = jax.random.PRNGKey(0)
    p = mamba2_params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, 24, cfg.d_model)) * 0.3

    out_ref, state_ref, conv_ref = mamba2_mix(cfg, p, x)
    assert state_ref is not None

    dispatch.set_backend("interpret")
    try:
        with dispatch.forward_ad_region():
            out_k, state_k, conv_k = mamba2_mix(cfg, p, x)
    finally:
        dispatch.set_backend(None)
    assert state_k is None
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(conv_k), np.asarray(conv_ref))


def test_mamba2_reverse_mode_unaffected():
    """jax.grad through dispatch.mamba2_mix (outside the region) must work
    on every backend — the jnp-mirror jvp rule is transposable."""
    (xdt, bm, cm, dec), _ = _problem(B=1, S=32, H=2, hd=8, N=8, T=1)

    def loss(x_):
        return jnp.mean(dispatch.mamba2_mix(x_, bm, cm, dec) ** 2)

    g_ref = jax.grad(loss)(xdt)
    for backend in ("interpret", "pallas"):
        dispatch.set_backend(backend)
        try:
            np.testing.assert_allclose(np.asarray(jax.grad(loss)(xdt)),
                                       np.asarray(g_ref), rtol=1e-6)
        finally:
            dispatch.set_backend(None)
