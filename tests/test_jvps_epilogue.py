"""In-kernel jvp-contraction epilogues (ISSUE 4).

Covers: the ``*_mt_jvps`` epilogue kernels against their
materialize-then-contract jnp oracles (allclose at fp32-accumulator
precision, and BITWISE equality of T stacked tangents vs T single-tangent
epilogue passes — each lane runs the exact op sequence of the T=1 slice);
the dispatch cotangent-known route — vmap of ``*_jvp_contract`` tangents
inside ``forward_ad_region()`` must trace ONE ``_jvps`` pallas_call whose
outputs are per-block partials, with NO (K, ..., N) tangent output buffer
anywhere in the jaxpr; and the estimator-level fused route
(``SplitLoss`` + ``forward_gradient(fused_contraction=True)``) against the
standard materializing route, including the padded chunked scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _wkv6_decay import wkv6_decay

from repro.analysis import (
    assert_no_tangent_stack,
    family_pallas_calls,
    kernel_src,
    pallas_calls,
    tangent_stack_outputs,
)
from repro.core.forward_grad import (
    SplitLoss,
    forward_gradient,
    fused_linearize,
)
from repro.kernels import dispatch
from repro.kernels.lora_dual import lora_dual_mt_jvps, lora_dual_mt_jvps_ref
from repro.kernels.mamba2_scan import (
    mamba2_scan_mt_jvps,
    mamba2_scan_mt_jvps_ref,
)
from repro.kernels.swa_attention import (
    swa_attention_mt_jvps,
    swa_attention_mt_jvps_ref,
)
from repro.kernels.wkv6_scan import wkv6_scan_mt_jvps, wkv6_scan_mt_jvps_ref


def _lora_problem(M=8, K=48, N=40, r=2, T=5, seed=0, scale=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    a = jax.random.normal(ks[2], (K, r)) * 0.05
    b = jax.random.normal(ks[3], (r, N)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    bd = jax.random.normal(ks[5], (T, r, N)) * 0.05
    xd = jax.random.normal(ks[6], (T, M, K)) * 0.3
    gy = jax.random.normal(ks[7], (M, N))
    return (x, w, a, b), (xd, ad, bd), gy, scale


def _wkv_problem(B=2, S=96, H=2, hd=16, T=3, seed=0, decay="sigmoid"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 11)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) * 0.3
               for i in range(3))
    w = wkv6_decay(decay, ks[3], (B, S, H, hd))
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    rd, kd, vd = (jax.random.normal(ks[5 + i], (T, B, S, H, hd)) * 0.3
                  for i in range(3))
    wd = jax.random.normal(ks[8], (T, B, S, H, hd)) * 0.1
    ud = jax.random.normal(ks[9], (T, H, hd)) * 0.3
    gy = jax.random.normal(ks[10], (B, S, H, hd))
    return (r, k, v, w, u), (rd, kd, vd, wd, ud), gy


def _mamba2_problem(B=2, S=96, H=2, hd=16, N=8, T=3, seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    xdt = jax.random.normal(ks[0], (B, S, H, hd)) * 0.3
    bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
    cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    dec = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H)))
    xd = jax.random.normal(ks[4], (T, B, S, H, hd)) * 0.3
    bd = jax.random.normal(ks[5], (T, B, S, N)) * 0.3
    cd = jax.random.normal(ks[6], (T, B, S, N)) * 0.3
    dd = jax.random.normal(ks[7], (T, B, S, H)) * 0.1
    gy = jax.random.normal(ks[8], (B, S, H, hd))
    return (xdt, bm, cm, dec), (xd, bd, cd, dd), gy


def _swa_problem(B=1, H=4, KV=2, S=128, hd=32, T=3, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    qd = jax.random.normal(ks[3], (T, B, H, S, hd))
    kd = jax.random.normal(ks[4], (T, B, KV, S, hd))
    vd = jax.random.normal(ks[5], (T, B, KV, S, hd))
    gy = jax.random.normal(ks[6], (B, H, S, hd))
    return (q, k, v), (qd, kd, vd), gy


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


# ---------------------------------------------------------------------------
# lora epilogue kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("has_xd", [True, False])
def test_lora_jvps_kernel_matches_oracle(has_xd):
    (x, w, a, b), (xd, ad, bd), gy, scale = _lora_problem()
    xd = xd if has_xd else None
    jk = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, scale=scale, xdots=xd,
                           impl="kernel", interpret=True)
    jo = lora_dual_mt_jvps_ref(x, w, a, ad, b, bd, gy, scale, xdots=xd)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jo), rtol=2e-5,
                               atol=1e-6)
    # and against the reassociated jnp mirror (the dispatch 'jnp' route)
    jr = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, scale=scale, xdots=xd,
                           impl="reassoc", interpret=True)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jr), rtol=2e-5,
                               atol=1e-6)


def test_lora_jvps_kernel_multiblock():
    """Shapes spanning several (bm, bn, bk) tiles exercise the blockwise
    partial accumulation + host-side partial sum."""
    (x, w, a, b), (xd, ad, bd), gy, scale = _lora_problem(
        M=200, K=130, N=70, r=4, T=3, seed=3)
    jk = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, scale=scale, xdots=xd,
                           impl="kernel", block_m=64, block_n=64, block_k=64,
                           interpret=True)
    jo = lora_dual_mt_jvps_ref(x, w, a, ad, b, bd, gy, scale, xdots=xd)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jo), rtol=2e-5,
                               atol=1e-6)


def test_lora_jvps_stacked_bitwise_equals_single_tangent_passes():
    """Each tangent lane of the epilogue runs the exact T=1 op sequence on
    independent accumulator rows — stacked partials are BITWISE equal to T
    single-tangent epilogue passes."""
    (x, w, a, b), (xd, ad, bd), gy, scale = _lora_problem()
    T = ad.shape[0]
    jk = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, scale=scale, xdots=xd,
                           impl="kernel", interpret=True)
    ones = jnp.concatenate([
        lora_dual_mt_jvps(x, w, a, ad[t:t + 1], b, bd[t:t + 1], gy,
                          scale=scale, xdots=xd[t:t + 1], impl="kernel",
                          interpret=True)
        for t in range(T)])
    np.testing.assert_array_equal(np.asarray(jk), np.asarray(ones))


# ---------------------------------------------------------------------------
# wkv6 epilogue kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ud,S,decay,T", [
    pytest.param(True, 96, "sigmoid", 3, id="True-96"),
    pytest.param(False, 96, "sigmoid", 3, id="False-96"),
    pytest.param(True, 75, "sigmoid", 3, id="True-75"),
    pytest.param(True, 96, "strong", 3, id="strong-True-96"),
    pytest.param(False, 75, "strong", 3, id="strong-False-75"),
    pytest.param(True, 96, "near1", 3, id="near1-True-96"),
    pytest.param(True, 10, "sigmoid", 3, id="True-10"),
    pytest.param(False, 96, "sigmoid", 1, id="T1-False-96"),
    pytest.param(True, 96, "sigmoid", 1, id="T1-True-96"),
    pytest.param(False, 96, "sigmoid", 8, id="T8-False-96"),
    pytest.param(True, 96, "sigmoid", 8, id="T8-True-96"),
])
def test_wkv6_jvps_kernel_matches_oracle(with_ud, S, decay, T):
    (r, k, v, w, u), (rd, kd, vd, wd, ud), gy = _wkv_problem(S=S, T=T,
                                                             decay=decay)
    uds = ud if with_ud else None
    jk = wkv6_scan_mt_jvps(r, k, v, w, u, rd, kd, vd, wd, gy, uds,
                           block_s=32, interpret=True)
    jo = wkv6_scan_mt_jvps_ref(r, k, v, w, u, rd, kd, vd, wd, gy, uds)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jo), rtol=2e-5,
                               atol=1e-5)


def test_wkv6_jvps_stacked_bitwise_equals_single_tangent_passes():
    (r, k, v, w, u), (rd, kd, vd, wd, ud), gy = _wkv_problem()
    T = rd.shape[0]
    jk = wkv6_scan_mt_jvps(r, k, v, w, u, rd, kd, vd, wd, gy, ud, block_s=32,
                           interpret=True)
    ones = jnp.concatenate([
        wkv6_scan_mt_jvps(r, k, v, w, u, rd[t:t + 1], kd[t:t + 1],
                          vd[t:t + 1], wd[t:t + 1], gy, ud[t:t + 1],
                          block_s=32, interpret=True)
        for t in range(T)])
    np.testing.assert_array_equal(np.asarray(jk), np.asarray(ones))


# ---------------------------------------------------------------------------
# mamba2 epilogue kernel (ISSUE 5 satellite: the last mt family without one)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [96, 75])
def test_mamba2_jvps_kernel_matches_oracle(S):
    (xdt, bm, cm, dec), (xd, bd, cd, dd), gy = _mamba2_problem(S=S)
    jk = mamba2_scan_mt_jvps(xdt, bm, cm, dec, xd, bd, cd, dd, gy,
                             block_s=32, interpret=True)
    jo = mamba2_scan_mt_jvps_ref(xdt, bm, cm, dec, xd, bd, cd, dd, gy)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jo), rtol=2e-5,
                               atol=1e-5)


def test_mamba2_jvps_stacked_bitwise_equals_single_tangent_passes():
    (xdt, bm, cm, dec), (xd, bd, cd, dd), gy = _mamba2_problem()
    T = xd.shape[0]
    jk = mamba2_scan_mt_jvps(xdt, bm, cm, dec, xd, bd, cd, dd, gy,
                             block_s=32, interpret=True)
    ones = jnp.concatenate([
        mamba2_scan_mt_jvps(xdt, bm, cm, dec, xd[t:t + 1], bd[t:t + 1],
                            cd[t:t + 1], dd[t:t + 1], gy, block_s=32,
                            interpret=True)
        for t in range(T)])
    np.testing.assert_array_equal(np.asarray(jk), np.asarray(ones))


def test_mamba2_contract_jnp_route_matches_oracle():
    (xdt, bm, cm, dec), (xd, bd, cd, dd), gy = _mamba2_problem()
    jo = mamba2_scan_mt_jvps_ref(xdt, bm, cm, dec, xd, bd, cd, dd, gy)
    dispatch.set_backend("jnp")
    try:
        vals = jax.vmap(lambda a, b, c, d: dispatch.mamba2_jvp_contract(
            gy, xdt, bm, cm, dec, a, b, c, d))(xd, bd, cd, dd)
    finally:
        dispatch.set_backend(None)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(jo), rtol=2e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# swa epilogue kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,S,force_pad",
                         [(48, 128, False), (None, 128, False),
                          (48, 100, False), (48, 128, True)])
def test_swa_jvps_kernel_matches_oracle(window, S, force_pad):
    (q, k, v), (qd, kd, vd), gy = _swa_problem(S=S)
    jk = swa_attention_mt_jvps(q, k, v, qd, kd, vd, gy, window=window,
                               block_q=64, block_k=64,
                               force_pad_hd=force_pad, interpret=True)
    jo = swa_attention_mt_jvps_ref(q, k, v, qd, kd, vd, gy, window=window)
    np.testing.assert_allclose(np.asarray(jk), np.asarray(jo), rtol=2e-4,
                               atol=1e-4)


def test_swa_jvps_stacked_bitwise_equals_single_tangent_passes():
    (q, k, v), (qd, kd, vd), gy = _swa_problem()
    T = qd.shape[0]
    jk = swa_attention_mt_jvps(q, k, v, qd, kd, vd, gy, window=48,
                               block_q=64, block_k=64, interpret=True)
    ones = jnp.concatenate([
        swa_attention_mt_jvps(q, k, v, qd[t:t + 1], kd[t:t + 1],
                              vd[t:t + 1], gy, window=48, block_q=64,
                              block_k=64, interpret=True)
        for t in range(T)])
    np.testing.assert_array_equal(np.asarray(jk), np.asarray(ones))


# ---------------------------------------------------------------------------
# dispatch: cotangent-known route (vmap-of-tangents -> ONE _jvps call,
# NO (K, ..., N) tangent output anywhere)
#
# jaxpr inspection goes through the shared repro.analysis pass; the old
# per-test _walk_eqns/_pallas_calls/_assert_no_tangent_stack_output
# helpers live there now.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["lora", "wkv6", "swa", "mamba2"])
def test_vmap_of_contract_traces_jvps_epilogue(kind):
    """vmap of a ``*_jvp_contract`` op's tangents inside
    ``forward_ad_region()`` must lower to ONE ``_jvps`` epilogue
    pallas_call whose outputs are per-block (..., K) partials — and the
    jaxpr must contain no (K,)+y.shape buffer at all."""
    K = 4
    if kind == "mamba2":
        (xdt, bm, cm, dec), _, gy = _mamba2_problem(B=1, S=32, H=2, hd=8,
                                                    N=4, T=1)
        y_shape = gy.shape

        def contract(xd, bd, cd, dd):
            return dispatch.mamba2_jvp_contract(gy, xdt, bm, cm, dec, xd,
                                                bd, cd, dd)

        tangents = (jnp.zeros((K,) + xdt.shape), jnp.zeros((K,) + bm.shape),
                    jnp.zeros((K,) + cm.shape), jnp.zeros((K,) + dec.shape))
    elif kind == "lora":
        (x, w, a, b), _, gy, scale = _lora_problem()
        y_shape = gy.shape

        def contract(ad, bd):
            return dispatch.lora_jvp_contract(gy, x, w, a, b, ad, bd,
                                              scale=scale)

        tangents = (jnp.zeros((K,) + a.shape), jnp.zeros((K,) + b.shape))
    elif kind == "wkv6":
        (r, k, v, w, u), _, gy = _wkv_problem(B=1, S=32, H=2, hd=8, T=1)
        y_shape = gy.shape

        def contract(rd, kd, vd, wd):
            return dispatch.wkv6_jvp_contract(gy, r, k, v, w, u, rd, kd, vd,
                                              wd)

        tangents = tuple(jnp.zeros((K,) + r.shape) for _ in range(4))
    else:
        (q, kk, vv), _, gy = _swa_problem(B=1, H=2, KV=2, S=64, hd=8, T=1)
        y_shape = gy.shape

        def contract(qd, kd, vd):
            return dispatch.swa_jvp_contract(gy, q, kk, vv, qd, kd, vd, 32)

        tangents = (jnp.zeros((K,) + q.shape),
                    jnp.zeros((K,) + kk.shape), jnp.zeros((K,) + vv.shape))

    dispatch.set_backend("interpret")
    try:
        with dispatch.forward_ad_region():
            jaxpr = jax.make_jaxpr(jax.vmap(contract))(*tangents)
    finally:
        dispatch.set_backend(None)

    calls = pallas_calls(jaxpr)
    assert len(calls) == 1, f"expected ONE _jvps pallas_call, got {calls}"
    (out_aval,) = [v.aval for v in calls[0].outvars]
    # per-block partials: trailing (tangent, 1) axes, tiny total size
    assert out_aval.shape[-2:] == (K, 1)
    assert_no_tangent_stack(jaxpr, K, y_shape)


# ---------------------------------------------------------------------------
# estimator: fused route == standard route; padded chunked scan; HBM claim
# ---------------------------------------------------------------------------

def _mixer_split_problem(kind, seed=2):
    B, S, H, hd = 1, 64, 2, 16
    N = 8
    D = H * hd
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    x = jax.random.normal(ks[0], (B, S, D)) * 0.3
    wp = [jax.random.normal(ks[1 + i], (D, D)) * 0.05 for i in range(3)]
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    wdec = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H, hd)))
    peft = {"A": jax.random.normal(ks[6], (D, 2)) * 0.05,
            "B": jax.random.normal(ks[7], (2, D)) * 0.05}
    wbc = [jax.random.normal(ks[8 + i], (D, N)) * 0.3 for i in range(2)]

    if kind == "lora":
        split = SplitLoss(lambda p: ((x, wp[0], p["A"], p["B"]), None),
                          "lora", lambda y, ctx, p: jnp.mean(y * y),
                          scale=2.0, x_has_tangent=False)
        return split, peft

    def pre(p):
        r = dispatch.lora_proj(x, wp[0], p["A"], p["B"], 2.0)
        k = (x @ wp[1]).reshape(B, S, H, hd)
        v = (x @ wp[2]).reshape(B, S, H, hd)
        if kind == "wkv6":
            return (r.reshape(B, S, H, hd), k, v, wdec, u), None
        if kind == "mamba2":
            return (r.reshape(B, S, H, hd), x @ wbc[0], x @ wbc[1],
                    wdec.mean(-1)), None
        return (r.reshape(B, S, H, hd).transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)), None

    split = SplitLoss(pre, kind, lambda y, ctx, p: jnp.mean(y * y),
                      window=32)
    return split, peft


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
@pytest.mark.parametrize("kind", ["lora", "wkv6", "swa", "mamba2"])
def test_fused_route_matches_standard(kind, backend):
    """fused_contraction on/off must produce the same loss (bitwise — the
    primal path is shared) and the same jvp scalars per seed up to float
    reassociation of the contraction."""
    split, peft = _mixer_split_problem(kind)
    key = jax.random.PRNGKey(9)
    dispatch.set_backend(backend)
    try:
        l0, g0, j0 = forward_gradient(split, peft, key, k_perturbations=4)
        l1, g1, j1 = forward_gradient(split, peft, key, k_perturbations=4,
                                      fused_contraction=True)
    finally:
        dispatch.set_backend(None)
    assert np.asarray(l0) == np.asarray(l1)
    assert _rel(j1, j0) < 1e-5
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


@pytest.mark.parametrize("kind", ["lora", "wkv6", "swa", "mamba2"])
def test_fused_chunked_scan_matches_full_batch(kind):
    """K=5 with tangent_batch=2 pads to 3 scanned groups with a masked-out
    lane; on the interpret backend (kernel lanes are exact replicas) the
    jvps must be BITWISE equal to the full-batch fused pass."""
    split, peft = _mixer_split_problem(kind)
    key = jax.random.PRNGKey(9)
    dispatch.set_backend("interpret")
    try:
        _, g2, j2 = forward_gradient(split, peft, key, k_perturbations=5,
                                     tangent_batch=2, fused_contraction=True)
        _, g3, j3 = forward_gradient(split, peft, key, k_perturbations=5,
                                     fused_contraction=True)
    finally:
        dispatch.set_backend(None)
    np.testing.assert_array_equal(np.asarray(j2), np.asarray(j3))
    for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_fused_k1_route():
    split, peft = _mixer_split_problem("lora")
    key = jax.random.PRNGKey(3)
    l0, g0, j0 = forward_gradient(split, peft, key, k_perturbations=1)
    l1, g1, j1 = forward_gradient(split, peft, key, k_perturbations=1,
                                  fused_contraction=True)
    assert j1.shape == (1,)
    assert _rel(j1, j0) < 1e-5


def test_split_loss_is_drop_in_callable():
    """SplitLoss(p) must equal the plain composition through the dispatched
    site op — BITWISE (same ops)."""
    split, peft = _mixer_split_problem("wkv6")

    def plain(p):
        args, ctx = split.pre(p)
        return jnp.mean(dispatch.wkv6_mix(*args) ** 2)

    np.testing.assert_array_equal(np.asarray(split(peft)),
                                  np.asarray(plain(peft)))


def test_fused_route_with_x_tangent():
    """x_has_tangent=True (x depends on the trainable tree via an upstream
    projection) exercises the epilogue's incremental frozen-W contraction."""
    B, S = 4, 16
    D = 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x0 = jax.random.normal(ks[0], (B * S, D)) * 0.3
    w0 = jax.random.normal(ks[1], (D, D)) * 0.05
    w1 = jax.random.normal(ks[2], (D, D)) * 0.05
    peft = {"A0": jax.random.normal(ks[3], (D, 2)) * 0.05,
            "B0": jnp.zeros((2, D)),
            "A1": jax.random.normal(ks[4], (D, 2)) * 0.05,
            "B1": jnp.zeros((2, D))}
    # two stacked LoRA projections: the SECOND is the fused site and its x
    # input carries tangents from the first
    def pre(p):
        h = dispatch.lora_proj(x0, w0, p["A0"], p["B0"], 2.0)
        return (h, w1, p["A1"], p["B1"]), None

    split = SplitLoss(pre, "lora", lambda y, ctx, p: jnp.mean(y * y),
                      scale=2.0, x_has_tangent=True)
    key = jax.random.PRNGKey(5)
    for backend in ("interpret", "jnp"):
        dispatch.set_backend(backend)
        try:
            l0, g0, j0 = forward_gradient(split, peft, key,
                                          k_perturbations=4)
            l1, g1, j1 = forward_gradient(split, peft, key,
                                          k_perturbations=4,
                                          fused_contraction=True)
        finally:
            dispatch.set_backend(None)
        assert _rel(j1, j0) < 1e-5, backend


@pytest.mark.parametrize("kind", ["lora", "wkv6", "swa", "mamba2"])
def test_fused_route_jaxpr_has_no_tangent_stack_at_site(kind):
    """The acceptance claim: on the fused-contraction route, NO
    (K, ..., N) tangent output buffer exists at the epilogue-eligible site
    — asserted on the traced jaxpr of the vmapped fused tangent fn. The
    standard route's jaxpr DOES contain it (sanity check that the
    assertion has teeth)."""
    K = 4
    split, peft = _mixer_split_problem(kind)
    peft32 = jax.tree.map(lambda t: t.astype(jnp.float32), peft)
    y_shape = np.asarray(split(peft)).shape  # scalar loss — need site shape
    args, _ = split.pre(peft32)
    y_shape = split.site(args).shape
    vs = jax.tree.map(lambda t: jnp.zeros((K,) + t.shape, jnp.float32),
                      peft32)

    dispatch.set_backend("interpret")
    try:
        _, fused_map = fused_linearize(split, peft32)
        fused_jaxpr = jax.make_jaxpr(jax.vmap(fused_map))(vs)
        with dispatch.forward_ad_region():
            _, std_map = jax.linearize(split, peft32)
        std_jaxpr = jax.make_jaxpr(jax.vmap(std_map))(vs)
    finally:
        dispatch.set_backend(None)

    family = {"lora": "lora_dual", "wkv6": "wkv6_scan",
              "swa": "swa_attention", "mamba2": "mamba2_scan"}[kind]

    # upstream (non-site) mixers in ``pre`` legitimately materialize their
    # tangents — only the SITE family's kernels are under test
    fused_site = family_pallas_calls(fused_jaxpr, family)
    assert fused_site, "fused route lost the site kernel entirely"
    for eqn in fused_site:
        assert "_mt_jvps_kernel" in kernel_src(eqn)
    assert_no_tangent_stack(fused_jaxpr, K, y_shape, family=family)
    assert tangent_stack_outputs(std_jaxpr, K, y_shape, family=family), (
        "standard route should materialize the site tangent stack — the "
        "no-stack assertion would be vacuous")
