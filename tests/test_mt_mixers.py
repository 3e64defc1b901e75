"""Multi-tangent wkv6/swa kernels + estimator dispatch (ISSUE 2).

Covers: mt-kernel oracles (allclose vs jax.jvp of the jnp reference, and
BITWISE equality of T stacked tangents vs T single-tangent kernel passes),
the GQA no-repeat kernel path vs the model's contiguous-group convention,
the forced padded-lane dataflow under interpret, and the dispatch routing —
vmap of tangents inside ``forward_ad_region()`` must trace ONE multi-tangent
pallas_call (leading T=K axis), not the Pallas default vmap lowering.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _wkv6_decay import wkv6_decay

from repro.analysis import pallas_calls
from repro.core.forward_grad import forward_gradient
from repro.kernels import dispatch
from repro.kernels.swa_attention import (
    swa_attention,
    swa_attention_gqa_ref,
    swa_attention_mt,
    swa_attention_mt_ref,
    swa_attention_mt_tangents,
    swa_attention_ref,
)
from repro.kernels.wkv6_scan import (
    wkv6_scan_mt,
    wkv6_scan_mt_ref,
    wkv6_scan_mt_tangents,
)


def _wkv_problem(B=2, S=96, H=2, hd=16, T=3, seed=0, decay="sigmoid"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) * 0.3 for i in range(3))
    w = wkv6_decay(decay, ks[3], (B, S, H, hd))
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    rd, kd, vd = (jax.random.normal(ks[5 + i], (T, B, S, H, hd)) * 0.3
                  for i in range(3))
    wd = jax.random.normal(ks[8], (T, B, S, H, hd)) * 0.1
    ud = jax.random.normal(ks[9], (T, H, hd)) * 0.3
    return (r, k, v, w, u), (rd, kd, vd, wd, ud)


def _swa_problem(B=1, H=4, KV=2, S=128, hd=32, T=3, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    qd = jax.random.normal(ks[3], (T, B, H, S, hd))
    kd = jax.random.normal(ks[4], (T, B, KV, S, hd))
    vd = jax.random.normal(ks[5], (T, B, KV, S, hd))
    return (q, k, v), (qd, kd, vd)


# ---------------------------------------------------------------------------
# wkv6 multi-tangent kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ud,decay,S,T", [
    pytest.param(False, "sigmoid", 96, 3, id="False"),
    pytest.param(True, "sigmoid", 96, 3, id="True"),
    pytest.param(False, "strong", 96, 3, id="strong"),
    pytest.param(True, "strong", 96, 3, id="strong-ud"),
    pytest.param(True, "strong", 75, 3, id="strong-ud-S75"),
    pytest.param(False, "near1", 96, 3, id="near1"),
    pytest.param(True, "near1", 96, 3, id="near1-ud"),
    pytest.param(True, "sigmoid", 10, 3, id="S10-ud"),
    pytest.param(False, "sigmoid", 96, 1, id="T1"),
    pytest.param(True, "sigmoid", 96, 1, id="T1-ud"),
    pytest.param(False, "sigmoid", 96, 8, id="T8"),
    pytest.param(True, "sigmoid", 96, 8, id="T8-ud"),
])
def test_wkv6_mt_matches_jvp_oracle(with_ud, decay, S, T):
    """Against T jax.jvp calls of the sequential oracle: w down to 1e-30 and
    exactly 0, w at 1 - 1e-6, S under one sub-chunk, S = 96 across six
    sub-chunks and three grid blocks (the state carry), T = 1 and 8."""
    (r, k, v, w, u), (rd, kd, vd, wd, ud) = _wkv_problem(S=S, T=T,
                                                         decay=decay)
    uds = ud if with_ud else None
    y, yds = wkv6_scan_mt(r, k, v, w, u, rd, kd, vd, wd, uds, block_s=32,
                          interpret=True)
    yr, ydr = wkv6_scan_mt_ref(r, k, v, w, u, rd, kd, vd, wd, uds)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr), atol=1e-5,
                               rtol=1e-5)


def test_wkv6_mt_odd_seq_padding():
    """Non-block-multiple S exercises the padded-step state preservation
    (w=1 keeps S, wd=0/kvd=0 keep every Sd)."""
    (r, k, v, w, u), (rd, kd, vd, wd, ud) = _wkv_problem(S=75)
    y, yds = wkv6_scan_mt(r, k, v, w, u, rd, kd, vd, wd, ud, block_s=32,
                          interpret=True)
    yr, ydr = wkv6_scan_mt_ref(r, k, v, w, u, rd, kd, vd, wd, ud)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr), atol=1e-5,
                               rtol=1e-5)


def test_wkv6_mt_stacked_bitwise_equals_single_tangent_passes():
    """T stacked tangents must be BITWISE equal to T single-tangent kernel
    passes (each tangent lane runs the exact T=1 op sequence on independent
    scratch) — the batched estimate is exactly K column-by-column jvps."""
    (r, k, v, w, u), (rd, kd, vd, wd, ud) = _wkv_problem()
    T = rd.shape[0]
    yds = wkv6_scan_mt_tangents(r, k, v, w, u, rd, kd, vd, wd, ud, block_s=32,
                                interpret=True)
    for t in range(T):
        one = wkv6_scan_mt_tangents(r, k, v, w, u, rd[t:t + 1], kd[t:t + 1],
                                    vd[t:t + 1], wd[t:t + 1], ud[t:t + 1],
                                    block_s=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(yds[t]), np.asarray(one[0]))


def test_wkv6_mt_tangents_match_full_pass():
    (r, k, v, w, u), (rd, kd, vd, wd, ud) = _wkv_problem(seed=5)
    _, yds = wkv6_scan_mt(r, k, v, w, u, rd, kd, vd, wd, ud, block_s=32,
                          interpret=True)
    ydt = wkv6_scan_mt_tangents(r, k, v, w, u, rd, kd, vd, wd, ud, block_s=32,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(yds), np.asarray(ydt))


# ---------------------------------------------------------------------------
# swa multi-tangent kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 48, 96])
def test_swa_mt_matches_jvp_oracle(window):
    (q, k, v), (qd, kd, vd) = _swa_problem()
    out, outds = swa_attention_mt(q, k, v, qd, kd, vd, window=window,
                                  block_q=64, block_k=64, interpret=True)
    outr, outdr = swa_attention_mt_ref(q, k, v, qd, kd, vd, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(np.asarray(outds), np.asarray(outdr),
                               atol=2e-3, rtol=2e-3)


def test_swa_mt_odd_seq_padding():
    """Non-block-multiple S exercises the query/key zero-padding (padded
    keys sit beyond every real query's causal band)."""
    (q, k, v), (qd, kd, vd) = _swa_problem(S=100, seed=7)
    out, outds = swa_attention_mt(q, k, v, qd, kd, vd, window=48, block_q=64,
                                  block_k=64, interpret=True)
    outr, outdr = swa_attention_mt_ref(q, k, v, qd, kd, vd, window=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(np.asarray(outds), np.asarray(outdr),
                               atol=2e-3, rtol=2e-3)
    out2 = swa_attention(q, k, v, window=48, block_q=64, block_k=64,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(outr), atol=2e-3,
                               rtol=2e-3)


def test_swa_mixed_blocks_no_padding_explosion():
    """Clamped block sizes that don't nest (S=100 -> bq=100, bk=64) must not
    lcm-explode the padded sequence; the plan clamps to the smaller block."""
    from repro.kernels.swa_attention.ops import _block_plan
    bq, bk, pad_s = _block_plan(100, 128, 64)
    assert (bq, bk) == (64, 64) and pad_s == 28
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 100, 32))
    k = jax.random.normal(ks[1], (1, 2, 100, 32))
    v = jax.random.normal(ks[2], (1, 2, 100, 32))
    out = swa_attention(q, k, v, window=48, block_q=128, block_k=64,
                        interpret=True)
    ref = swa_attention_ref(q, k, v, window=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)


def test_swa_mt_stacked_bitwise_equals_single_tangent_passes():
    (q, k, v), (qd, kd, vd) = _swa_problem()
    T = qd.shape[0]
    outds = swa_attention_mt_tangents(q, k, v, qd, kd, vd, window=48,
                                      block_q=64, block_k=64, interpret=True)
    for t in range(T):
        one = swa_attention_mt_tangents(q, k, v, qd[t:t + 1], kd[t:t + 1],
                                        vd[t:t + 1], window=48, block_q=64,
                                        block_k=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(outds[t]),
                                      np.asarray(one[0]))


# ---------------------------------------------------------------------------
# GQA without K/V materialization (ISSUE 2 satellite bugfix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 2), (6, 3)])
def test_swa_gqa_parity_with_model_convention(H, KV):
    """The in-grid head mapping must agree with the model's contiguous-group
    jnp.repeat convention (models/attention.py::_sdpa) — head h reads kv
    head h // (H//KV) — with K/V never repeated in HBM on the kernel path."""
    ks = jax.random.split(jax.random.PRNGKey(H * 10 + KV), 3)
    B, S, hd, W = 2, 128, 32, 48
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    out = swa_attention(q, k, v, window=W, block_q=64, block_k=64,
                        interpret=True)
    rep = H // KV
    ref = swa_attention_ref(q, jnp.repeat(k, rep, axis=1),
                            jnp.repeat(v, rep, axis=1), window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)
    refg = swa_attention_gqa_ref(q, k, v, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refg), atol=2e-3,
                               rtol=2e-3)


def test_swa_kernel_path_has_no_repeat():
    """The H/KV× K/V materialization must be gone from the kernel path: the
    pallas_call's k/v operands stay at (B*KV, S, hd), and no repeat
    primitive appears anywhere in the traced jaxpr."""
    B, H, KV, S, hd = 1, 8, 2, 128, 128   # hd=128: no lane pad in the trace
    q = jnp.zeros((B, H, S, hd))
    k = jnp.zeros((B, KV, S, hd))
    v = jnp.zeros((B, KV, S, hd))
    jaxpr = jax.make_jaxpr(
        lambda q_, k_, v_: swa_attention(q_, k_, v_, window=48, block_q=64,
                                         block_k=64, interpret=True))(q, k, v)

    def walk(j):
        for eqn in j.eqns:
            yield eqn
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    yield from walk(inner if hasattr(inner, "eqns")
                                    else inner.jaxpr)

    calls = []
    for eqn in walk(jaxpr.jaxpr):
        assert "repeat" not in eqn.primitive.name, eqn
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
    assert len(calls) == 1
    q_aval, k_aval, v_aval = [var.aval for var in calls[0].invars[-3:]]
    assert q_aval.shape == (B * H, S, hd)
    assert k_aval.shape == (B * KV, S, hd), "k was widened before the kernel"
    assert v_aval.shape == (B * KV, S, hd), "v was widened before the kernel"


@pytest.mark.parametrize("hd", [96, 72])
def test_swa_forced_pad_hd_under_interpret(hd):
    """hd not a multiple of 128: forcing the lane pad under interpret must
    exercise the padded dataflow and still match the unpadded oracle."""
    ks = jax.random.split(jax.random.PRNGKey(hd), 3)
    B, H, S, W = 1, 2, 128, 64
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    out = swa_attention(q, k, v, window=W, block_q=64, block_k=64,
                        force_pad_hd=True, interpret=True)
    ref = swa_attention_ref(q, k, v, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)
    # the pad must actually be live: the kernel input gains padded lanes
    jaxpr = jax.make_jaxpr(
        lambda q_, k_, v_: swa_attention(q_, k_, v_, window=W, block_q=64,
                                         block_k=64, force_pad_hd=True,
                                         interpret=True))(
        q, k, v)
    assert f"{128 * ((hd + 127) // 128)}" in str(jaxpr)


def test_swa_mt_forced_pad_hd():
    (q, k, v), (qd, kd, vd) = _swa_problem(hd=48)
    out, outds = swa_attention_mt(q, k, v, qd, kd, vd, window=48, block_q=64,
                                  block_k=64, force_pad_hd=True,
                                  interpret=True)
    outr, outdr = swa_attention_mt_ref(q, k, v, qd, kd, vd, window=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(np.asarray(outds), np.asarray(outdr),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# dispatch: estimator routing (vmap-of-tangents -> ONE mt pallas_call)
# ---------------------------------------------------------------------------

def test_vmap_of_lora_tangents_traces_mt_route():
    """vmap of lora_proj tangents inside forward_ad_region() must lower to
    the multi-tangent kernel directly — ONE pallas_call whose tangent output
    carries the leading K axis (3-dim (K, M, N)) — and NOT the Pallas
    default vmap lowering of the T=1 kernel (which re-grids to a 4-dim
    (K, 1, M, N) output and recomputes per-tangent)."""
    K = 5
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (8, 48))
    w = jax.random.normal(ks[1], (48, 40)) * 0.05
    peft = {"A": jax.random.normal(ks[2], (48, 2)) * 0.05,
            "B": jax.random.normal(ks[3], (2, 40)) * 0.05}

    def loss_of(p):
        y = dispatch.lora_proj(x, w, p["A"], p["B"], 2.0)
        return jnp.mean(y * y)

    dispatch.set_backend("interpret")
    try:
        with dispatch.forward_ad_region():
            _, tangent_map = jax.linearize(loss_of, peft)
        vs = {"A": jnp.zeros((K,) + peft["A"].shape),
              "B": jnp.zeros((K,) + peft["B"].shape)}
        jaxpr = jax.make_jaxpr(jax.vmap(tangent_map))(vs)
    finally:
        dispatch.set_backend(None)

    calls = pallas_calls(jaxpr)
    assert len(calls) == 1, f"expected ONE fused mt pallas_call, got {calls}"
    (out_aval,) = [v.aval for v in calls[0].outvars]
    assert out_aval.ndim == 3 and out_aval.shape[0] == K, (
        f"tangent output {out_aval.shape} is not the (K, M, N) mt contract "
        "— the default Pallas batching rule was used")


@pytest.mark.parametrize("mixer", ["wkv6", "swa"])
def test_vmap_of_mixer_tangents_traces_mt_route(mixer):
    """Same routing assertion for the sequence mixers: the batched
    estimator's vmap must hit wkv6_scan_mt_tangents /
    swa_attention_mt_tangents (leading-K tangent outputs), not a re-gridded
    T=1 kernel."""
    K = 4
    if mixer == "wkv6":
        (r, k, v, w, u), _ = _wkv_problem(B=1, S=32, H=2, hd=8, T=1)

        def f(rkv):
            return jnp.mean(
                dispatch.wkv6_mix(rkv["r"], rkv["k"], rkv["v"], w, u) ** 2)

        prim = {"r": r, "k": k, "v": v}
    else:
        (q, kk, vv), _ = _swa_problem(B=1, H=2, KV=2, S=64, hd=8, T=1)

        def f(rkv):
            return jnp.mean(
                dispatch.swa_attend(rkv["q"], rkv["k"], rkv["v"], 32) ** 2)

        prim = {"q": q, "k": kk, "v": vv}

    dispatch.set_backend("interpret")
    try:
        with dispatch.forward_ad_region():
            _, tangent_map = jax.linearize(f, prim)
        vs = jax.tree.map(lambda t: jnp.zeros((K,) + t.shape), prim)
        jaxpr = jax.make_jaxpr(jax.vmap(tangent_map))(vs)
    finally:
        dispatch.set_backend(None)

    calls = pallas_calls(jaxpr)
    assert len(calls) == 1, f"expected ONE fused mt pallas_call, got {calls}"
    (out_aval,) = [v.aval for v in calls[0].outvars]
    assert out_aval.shape[0] == K, (
        f"tangent output {out_aval.shape} does not carry the leading K axis")


@pytest.mark.parametrize("mixer", ["wkv6", "swa"])
def test_mixer_estimator_batched_jvps_bitwise_equal_sequential(mixer):
    """The batched K-tangent estimate through a dispatched mixer must give
    jvps BITWISE equal to the sequential tangent_batch=1 run (the
    column-by-column baseline) on the interpret backend — per-tangent kernel
    lanes are exact replicas of the T=1 pass."""
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    B, S, H, hd = 1, 64, 2, 16
    D = H * hd
    x = jax.random.normal(ks[0], (B, S, D)) * 0.3
    wp = [jax.random.normal(ks[1 + i], (D, D)) * 0.05 for i in range(3)]
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    wdec = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H, hd)))
    peft = {"A": jax.random.normal(ks[6], (D, 2)) * 0.05,
            "B": jax.random.normal(ks[7], (2, D)) * 0.05}

    def loss(p):
        r = dispatch.lora_proj(x, wp[0], p["A"], p["B"], 2.0)
        k = (x @ wp[1]).reshape(B, S, H, hd)
        v = (x @ wp[2]).reshape(B, S, H, hd)
        if mixer == "wkv6":
            y = dispatch.wkv6_mix(r.reshape(B, S, H, hd), k, v, wdec, u)
        else:
            y = dispatch.swa_attend(
                r.reshape(B, S, H, hd).transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), 32)
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


def test_mixers_not_dispatched_outside_region_or_on_jnp():
    """Outside forward_ad_region(), and on the jnp backend, the model paths
    must stay on their native scan/chunked implementations (reverse-mode
    baselines depend on transposability)."""
    assert not dispatch.use_kernel_mixers()
    dispatch.set_backend("jnp")
    try:
        with dispatch.forward_ad_region():
            assert not dispatch.use_kernel_mixers()
    finally:
        dispatch.set_backend(None)
    dispatch.set_backend("interpret")
    try:
        assert not dispatch.use_kernel_mixers()
        with dispatch.forward_ad_region():
            assert dispatch.use_kernel_mixers()
    finally:
        dispatch.set_backend(None)


def test_mixer_reverse_mode_unaffected():
    """jax.grad through the dispatched ops (outside the region) must work on
    every backend — the jnp-mirror jvp rule is transposable."""
    (r, k, v, w, u), _ = _wkv_problem(B=1, S=32, H=2, hd=8, T=1)

    def loss_w(r_):
        return jnp.mean(dispatch.wkv6_mix(r_, k, v, w, u) ** 2)

    (q, kk, vv), _ = _swa_problem(B=1, H=2, KV=2, S=64, hd=8, T=1)

    def loss_s(q_):
        return jnp.mean(dispatch.swa_attend(q_, kk, vv, 32) ** 2)

    g_ref_w = jax.grad(loss_w)(r)
    g_ref_s = jax.grad(loss_s)(q)
    for backend in ("interpret", "pallas"):
        dispatch.set_backend(backend)
        try:
            np.testing.assert_allclose(np.asarray(jax.grad(loss_w)(r)),
                                       np.asarray(g_ref_w), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(jax.grad(loss_s)(q)),
                                       np.asarray(g_ref_s), rtol=1e-6)
        finally:
            dispatch.set_backend(None)
