"""Batched K-tangent engine: batched/chunked/sequential equivalence, the
multi-tangent lora_dual kernel vs its oracle, and client/server bit-identity
for the per-iteration communication mode (ISSUE 1 acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forward_grad import (
    forward_gradient,
    reconstruct_gradient,
    stacked_perturbations,
    masked_perturbation,
)
from repro.kernels.lora_dual import (
    lora_dual_mt,
    lora_dual_mt_jvps,
    lora_dual_mt_jvps_ref,
    lora_dual_mt_ref,
)


def quad_loss(w):
    A = jnp.arange(12.0).reshape(3, 4) / 10.0
    r = A @ w["w"] - jnp.ones(3)
    return 0.5 * jnp.sum(r * r) + jnp.sum(w["v"] ** 2)


@pytest.fixture()
def w():
    return {"w": jnp.array([1.0, -2.0, 0.5, 3.0]), "v": jnp.array([0.2, -0.1])}


# ---------------------------------------------------------------------------
# estimator equivalence across tangent_batch settings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 4, 8])
def test_batched_equals_sequential_per_seed(w, rng_key, K):
    """Same seed -> same perturbations -> allclose grads and jvps between the
    one-pass batched path and the sequential fori_loop path."""
    ls, gs, js = forward_gradient(quad_loss, w, rng_key, k_perturbations=K,
                                  tangent_batch=1)
    lb, gb, jb = forward_gradient(quad_loss, w, rng_key, k_perturbations=K,
                                  tangent_batch=None)
    np.testing.assert_allclose(np.asarray(js), np.asarray(jb), rtol=1e-6)
    np.testing.assert_allclose(float(ls), float(lb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("K,tb", [(4, 2), (8, 4), (6, 4), (5, 2)])
def test_chunked_equals_batched(w, rng_key, K, tb):
    """tangent_batch chunks (incl. non-divisible remainders) reproduce the
    fully batched estimate."""
    _, gb, jb = forward_gradient(quad_loss, w, rng_key, k_perturbations=K)
    _, gc, jc = forward_gradient(quad_loss, w, rng_key, k_perturbations=K,
                                 tangent_batch=tb)
    np.testing.assert_allclose(np.asarray(jc), np.asarray(jb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_stacked_perturbations_bit_identical_to_sequential(w, rng_key):
    """vmap of the PRNG chain must reproduce masked_perturbation bit-for-bit
    per index — the property the per-iteration comm mode relies on."""
    mask = {"w": jnp.ones(()), "v": jnp.zeros(())}
    vs = stacked_perturbations(rng_key, w, jnp.arange(5), mask)
    for i in range(5):
        vi = masked_perturbation(jax.random.fold_in(rng_key, i), w, mask)
        for a, b in zip(jax.tree.leaves(vi),
                        jax.tree.leaves(jax.tree.map(lambda x: x[i], vs))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("K", [1, 3, 8])
def test_client_server_bit_identity(w, rng_key, K):
    """Per-iteration mode: the server rebuild from (seed, jvps) must be
    BIT-identical to the client-side batched estimate (shared stacked
    sampling + combine contraction)."""
    mask = {"w": jnp.ones(()), "v": jnp.ones(())}
    _, g_client, jvps = forward_gradient(quad_loss, w, rng_key,
                                         k_perturbations=K, mask_tree=mask)
    g_server = reconstruct_gradient(w, rng_key, jvps, mask_tree=mask)
    for a, b in zip(jax.tree.leaves(g_client), jax.tree.leaves(g_server)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_client_server_bit_identity_with_clip(w, rng_key):
    _, g_client, jvps = forward_gradient(quad_loss, w, rng_key,
                                         k_perturbations=4, jvp_clip=0.1)
    assert float(jnp.abs(jvps).max()) <= float(jnp.float32(0.1))
    g_server = reconstruct_gradient(w, rng_key, jvps)
    for a, b in zip(jax.tree.leaves(g_client), jax.tree.leaves(g_server)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_through_scan_loss(rng_key):
    """The linearize+vmap path must flow through lax.scan model bodies."""
    def loss(w):
        def body(c, x):
            return jnp.tanh(c @ w["m"]) + x, None
        c, _ = jax.lax.scan(body, jnp.ones(3), jnp.zeros((5, 3)))
        return jnp.sum(c ** 2)

    w = {"m": jnp.eye(3) * 0.5}
    _, gs, js = forward_gradient(loss, w, rng_key, k_perturbations=4,
                                 tangent_batch=1)
    _, gb, jb = forward_gradient(loss, w, rng_key, k_perturbations=4)
    np.testing.assert_allclose(np.asarray(js), np.asarray(jb), rtol=1e-5,
                               atol=1e-7)
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# multi-tangent kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 4, 16])
@pytest.mark.parametrize("M,K,N,r", [(128, 128, 128, 4), (64, 192, 128, 8)])
def test_lora_dual_mt_allclose(M, K, N, r, T):
    ks = jax.random.split(jax.random.PRNGKey(M + T), 7)
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (T, M, K))
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (T, r, N)) * 0.05
    y, yds = lora_dual_mt(x, xd, w, a, ad, b, bd, scale=2.0, block_m=64,
                          block_n=64, block_k=64, interpret=True)
    yr, ydr = lora_dual_mt_ref(x, xd, w, a, ad, b, bd, 2.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr), atol=1e-3,
                               rtol=1e-3)


@pytest.mark.parametrize("T", [1, 4])
def test_lora_dual_mt_odd_shapes_and_no_xdot(T):
    """Padding path (non-block-multiple shapes) and the xdots=None variant
    (first perturbed unit: input carries no tangent)."""
    M, K, N, r = 111, 94, 77, 3
    ks = jax.random.split(jax.random.PRNGKey(T), 7)
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (T, M, K))
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (T, r, N)) * 0.05
    for xdots in (xd, None):
        y, yds = lora_dual_mt(x, xdots, w, a, ad, b, bd, scale=1.5,
                              block_m=64, block_n=64, block_k=64,
                              interpret=True)
        yr, ydr = lora_dual_mt_ref(x, xdots, w, a, ad, b, bd, 1.5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(yds), np.asarray(ydr),
                                   atol=1e-3, rtol=1e-3)


def test_lora_dual_mt_matches_columnwise_jvp():
    """ydots[t] must equal jax.jvp of the LoRA projection along tangent t —
    the batched pass is exactly K column-by-column jvps fused."""
    M, K, N, r, T = 64, 96, 80, 2, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (T, M, K))
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (T, r, N)) * 0.05

    def f(x_, a_, b_):
        return x_ @ w + 2.0 * (x_ @ a_) @ b_

    y, yds = lora_dual_mt(x, xd, w, a, ad, b, bd, scale=2.0, block_m=64,
                          block_n=64, block_k=64, interpret=True)
    for t in range(T):
        y_ref, yd_ref = jax.jvp(f, (x, a, b), (xd[t], ad[t], bd[t]))
        np.testing.assert_allclose(np.asarray(yds[t]), np.asarray(yd_ref),
                                   atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("with_xdot", [False, True])
def test_lora_dual_mt_jvps_fused_contraction(with_xdot):
    """The reassociated jvp contraction (no (T,M,N) materialization) must
    match contracting the materialized oracle ydots."""
    M, K, N, r, T = 96, 80, 64, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(ks[0], (M, K))
    xd = jax.random.normal(ks[1], (T, M, K)) if with_xdot else None
    w = jax.random.normal(ks[2], (K, N)) * 0.05
    a = jax.random.normal(ks[3], (K, r)) * 0.05
    ad = jax.random.normal(ks[4], (T, K, r)) * 0.05
    b = jax.random.normal(ks[5], (r, N)) * 0.05
    bd = jax.random.normal(ks[6], (T, r, N)) * 0.05
    gy = jax.random.normal(ks[7], (M, N))
    jv = lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, scale=2.0, xdots=xd,
                           interpret=True)
    jvr = lora_dual_mt_jvps_ref(x, w, a, ad, b, bd, gy, 2.0, xdots=xd)
    np.testing.assert_allclose(np.asarray(jv), np.asarray(jvr), rtol=1e-4,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# dispatch routing
# ---------------------------------------------------------------------------

def test_dispatch_jnp_vs_interpret_consistent():
    """proj's custom-JVP rule: jnp reference mirror and the interpreted
    Pallas kernel agree under forward-mode AD."""
    from repro.kernels import dispatch
    from repro.kernels.dispatch import lora_proj

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (4, 24, 48))
    w = jax.random.normal(ks[1], (48, 40)) * 0.05
    A = jax.random.normal(ks[2], (48, 2)) * 0.05
    B = jax.random.normal(ks[3], (2, 40)) * 0.05
    Ad = jax.random.normal(ks[4], (48, 2)) * 0.05
    Bd = jax.random.normal(ks[5], (2, 40)) * 0.05
    outs = {}
    for backend in ("jnp", "interpret"):
        dispatch.set_backend(backend)
        try:
            # the kernel tangent route is gated on the estimator's
            # forward-AD region (no transpose rule on pallas calls)
            with dispatch.forward_ad_region():
                outs[backend] = jax.jvp(
                    lambda a_, b_: lora_proj(x, w, a_, b_, 2.0), (A, B),
                    (Ad, Bd))
        finally:
            dispatch.set_backend(None)
    np.testing.assert_allclose(np.asarray(outs["jnp"][0]),
                               np.asarray(outs["interpret"][0]), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(outs["jnp"][1]),
                               np.asarray(outs["interpret"][1]), atol=1e-4,
                               rtol=1e-4)


def test_reverse_mode_works_on_kernel_backends():
    """jax.grad through lora_proj must work on every backend (the backprop
    baselines differentiate through proj in reverse mode; outside the
    forward-AD region the rule must trace the transposable jnp mirror)."""
    from repro.kernels import dispatch
    from repro.kernels.dispatch import lora_proj

    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (16, 24))
    w = jax.random.normal(ks[1], (24, 20)) * 0.05
    A = jax.random.normal(ks[2], (24, 2)) * 0.05
    B = jax.random.normal(ks[3], (2, 20)) * 0.05

    def loss(a_, b_):
        return jnp.sum(lora_proj(x, w, a_, b_, 2.0) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1))(A, B)
    for backend in ("interpret", "pallas"):
        dispatch.set_backend(backend)
        try:
            g = jax.grad(loss, argnums=(0, 1))(A, B)
        finally:
            dispatch.set_backend(None)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_proj_routes_through_dispatch(monkeypatch):
    """models.common.proj must call the dispatch layer for LoRA projections."""
    from repro.kernels import dispatch
    from repro.models.common import proj

    calls = []
    real = dispatch.lora_proj

    def spy(x, w, a, b, scale):
        calls.append(scale)
        return real(x, w, a, b, scale)

    import repro.models.common as common
    monkeypatch.setattr(common, "lora_proj", spy)
    x = jnp.ones((2, 8))
    w = jnp.ones((8, 4))
    lora = {"A": jnp.ones((8, 1)), "B": jnp.zeros((1, 4))}
    proj(x, w, lora=lora, lora_scale=3.0)
    assert calls == [3.0]
    proj(x, w)                      # no LoRA -> no dispatch
    assert calls == [3.0]
