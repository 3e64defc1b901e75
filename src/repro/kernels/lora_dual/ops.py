"""jit'd dispatch wrappers: flatten batch dims, pad to block multiples,
call the Pallas kernel, unpad.

``lora_dual``      single-tangent fused pass (y, ydot)
``lora_dual_mt``   multi-tangent fused pass (y, ydots (T, ...)) — one read
                   of x/W serves the primal and all T tangents
``lora_dual_mt_jvps``  fused jvp-contraction epilogue: all T jvp scalars
                   <gy, ydot_t> WITHOUT materializing any (T, M, N) tangent
                   output — the cheap path when the projection output feeds
                   a known cotangent (last-mixer / loss-head sites,
                   benchmarks). ``impl='kernel'`` runs the in-kernel
                   blockwise epilogue (``lora_dual_mt_jvps_kernel``: the
                   per-tangent partials accumulate in VMEM and only one
                   scalar per tangent per grid tile reaches HBM);
                   ``impl='reassoc'`` is the jnp mirror of the same
                   reassociated math (the fast XLA-fused CPU path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import sum_partials
from repro.kernels.lora_dual.kernel import (
    lora_dual_kernel,
    lora_dual_mt_jvps_kernel,
    lora_dual_mt_kernel,
    lora_dual_multi_kernel,
)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def lora_dual(x, xdot, w, a, adot, b, bdot, scale: float = 1.0,
              block_m: int = 128, block_n: int = 128, block_k: int = 128, *,
              interpret: bool):
    """Fused y = x@W + s(x@A)@B and its jvp. x may have leading batch dims."""
    y, ydots = lora_dual_mt(x, xdot[None], w, a, adot[None], b, bdot[None],
                            scale=scale, block_m=block_m, block_n=block_n,
                            block_k=block_k, interpret=interpret)
    return y, ydots[0]


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def lora_dual_mt(x, xdots, w, a, adots, b, bdots, scale: float = 1.0,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128, *,
                 interpret: bool):
    """Multi-tangent fused pass. x: (..., K); xdots: (T, ..., K) or None;
    adots: (T, K, r); bdots: (T, r, N) -> (y (..., N), ydots (T, ..., N))."""
    batch_shape = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    T = adots.shape[0]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]

    x2 = _pad_to(_pad_to(x2, block_m, 0), block_k, 1)
    if xdots is not None:
        xd2 = xdots.reshape(T, -1, K)
        xd2 = _pad_to(_pad_to(xd2, block_m, 1), block_k, 2)
    else:
        xd2 = None
    wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
    ap = _pad_to(a, block_k, 0)
    adp = _pad_to(adots, block_k, 1)
    bp = _pad_to(b, block_n, 1)
    bdp = _pad_to(bdots, block_n, 2)

    y, yds = lora_dual_mt_kernel(x2, xd2, wp, ap, adp, bp, bdp, scale=scale,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k, interpret=interpret)
    y = y[:M, :N].reshape(batch_shape + (N,))
    yds = yds[:, :M, :N].reshape((T,) + batch_shape + (N,))
    return y, yds


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def lora_dual_mt_tangents(x, xdots, w, a, adots, b, bdots, scale: float = 1.0,
                          block_m: int = 128, block_n: int = 128,
                          block_k: int = 128, *, interpret: bool):
    """Tangent-only fused pass -> ydots (T, ..., N). Same contract as
    ``lora_dual_mt`` but skips the primal output — the AD dispatch rule uses
    this so its primal stays a pure function of primal inputs (required for
    jax.linearize to partial-eval through the custom-JVP rule)."""
    batch_shape = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    T = adots.shape[0]
    x2 = _pad_to(_pad_to(x.reshape(-1, K), block_m, 0), block_k, 1)
    M = x.reshape(-1, K).shape[0]
    if xdots is not None:
        xdots = _pad_to(_pad_to(xdots.reshape(T, -1, K), block_m, 1),
                        block_k, 2)
    wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
    ap = _pad_to(a, block_k, 0)
    adp = _pad_to(adots, block_k, 1)
    bp = _pad_to(b, block_n, 1)
    bdp = _pad_to(bdots, block_n, 2)
    yds = lora_dual_mt_kernel(x2, xdots, wp, ap, adp, bp, bdp, scale=scale,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k, interpret=interpret,
                              emit_primal=False)
    return yds[:, :M, :N].reshape((T,) + batch_shape + (N,))


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def lora_dual_multi(x, idx, w, a_stack, b_stack, scale: float = 1.0,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128, *, interpret: bool):
    """Multi-adapter fused projection: each batch row reads its own LoRA
    page, one pass over the shared frozen W. x: (..., K); idx: adapter-page
    indices broadcastable to x.shape[:-1] (typically (B,) over a (B, S, K)
    batch); a_stack: (P, K, r); b_stack: (P, r, N) -> y (..., N)."""
    batch_shape = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    idx = jnp.reshape(idx, idx.shape + (1,) * (len(batch_shape) - idx.ndim))
    idx = jnp.broadcast_to(idx, batch_shape)
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    x2 = _pad_to(_pad_to(x2, block_m, 0), block_k, 1)
    # padded rows read page 0 over zero inputs; their outputs are discarded
    i2 = _pad_to(idx.reshape(-1, 1).astype(jnp.int32), block_m, 0)
    wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
    ap = _pad_to(a_stack, block_k, 1)
    bp = _pad_to(b_stack, block_n, 2)
    y = lora_dual_multi_kernel(x2, i2, wp, ap, bp, scale=scale,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, interpret=interpret)
    return y[:M, :N].reshape(batch_shape + (N,))


@functools.partial(jax.jit, static_argnames=("scale", "impl", "block_m",
                                             "block_n", "block_k",
                                             "interpret"))
def lora_dual_mt_jvps(x, w, a, adots, b, bdots, gy, scale: float = 1.0,
                      xdots=None, impl: str = "reassoc",
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, *, interpret: bool):
    """All T jvp scalars <gy, ydot_t> via the fused contraction epilogue.

    Never materializes a (T, M, N) tangent stack: the frozen-weight GEMM
    appears at most once (gy@Wᵀ, only when ``xdots`` is given) and every
    per-tangent term is rank-r sized. Equivalent (up to float reassociation)
    to contracting ``gy`` with ``lora_dual_mt``'s ydots — the oracle is
    ``ref.lora_dual_mt_jvps_ref``.

    ``impl='kernel'`` runs the blockwise Pallas epilogue
    (``lora_dual_mt_jvps_kernel``); ``impl='reassoc'`` is the whole-array
    jnp mirror of the same math (the fast CPU path the dispatch layer picks
    on the 'jnp' backend).
    """
    T = adots.shape[0]
    if impl == "kernel":
        x2 = x.reshape(-1, x.shape[-1])
        M, K = x2.shape
        N = w.shape[1]
        x2 = _pad_to(_pad_to(x2, block_m, 0), block_k, 1)
        if xdots is not None:
            xd2 = _pad_to(_pad_to(xdots.reshape(T, -1, K), block_m, 1),
                          block_k, 2)
        else:
            xd2 = None
        wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
        ap = _pad_to(a, block_k, 0)
        adp = _pad_to(adots, block_k, 1)
        bp = _pad_to(b, block_n, 1)
        bdp = _pad_to(bdots, block_n, 2)
        # zero-padded gy rows/cols contribute exactly 0 to every partial
        gy2 = _pad_to(_pad_to(gy.reshape(-1, N), block_m, 0), block_n, 1)
        parts = lora_dual_mt_jvps_kernel(
            x2, xd2, wp, ap, adp, bp, bdp, gy2, scale=scale,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret)
        return sum_partials(parts)

    x = x.reshape(-1, x.shape[-1])
    gy = gy.reshape(-1, gy.shape[-1]).astype(jnp.float32)
    u = x @ a                                       # (M, r)
    z1 = gy @ b.T                                   # (M, r)  ydot·b side
    z2 = u.T @ gy                                   # (r, N)  u·bdot side
    udots = x @ adots                               # (T, M, r)
    if xdots is not None:
        xdots = xdots.reshape(adots.shape[0], -1, x.shape[-1])
        udots = udots + xdots @ a
    jvps = scale * (jnp.einsum("mr,tmr->t", z1, udots)
                    + jnp.einsum("rn,trn->t", z2,
                                 bdots.astype(jnp.float32)))
    if xdots is not None:
        jvps = jvps + jnp.einsum("mk,tmk->t", gy @ w.T, xdots)
    return jvps
