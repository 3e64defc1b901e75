"""Fused LoRA primal + multi-tangent matmul — Pallas TPU kernel.

This is the TPU answer to the paper's §5.3 observation that PyTorch
Forward-mode AD pays a "column-by-column jvp" overhead: the tangent GEMMs
share the VMEM residency of the primal GEMM. The multi-tangent (mt) variant
extends that to SPRY's K-perturbation estimates — tangent operands
``xdot/adot/bdot`` carry a leading tangent axis T, and ONE pass over HBM for
``x``/``W`` produces the primal ``y`` plus all T ``ydot``s. The frozen-weight
GEMM (the overwhelming majority of FLOPs under LoRA) is read and computed
once instead of T times; the rank-r LoRA factors live entirely in VMEM
scratch across the K-reduction.

Tangent-axis contract: ``xdots (T, M, K)``, ``adots (T, K, r)``,
``bdots (T, r, N)`` -> ``ydots (T, M, N)``. ``has_xdot=False`` statically
removes the input-tangent GEMMs for the common SPRY case where the
projection is the client's first perturbed unit (upstream activations carry
no tangent).

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" = sequential reduction).
VMEM blocks are MXU-aligned (multiples of 128 on the matmul dims); the T
axis is unrolled statically (T <= ~16 keeps the (T, bm, bn) accumulator
within VMEM budget: 16*128*128*4B = 1 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_dot


def _mt_kernel(*refs, scale: float, n_k: int, n_t: int, has_xdot: bool,
               emit_primal: bool):
    refs = list(refs)
    x_ref = refs.pop(0)
    xd_ref = refs.pop(0) if has_xdot else None
    w_ref, a_ref, ad_ref, b_ref, bd_ref = refs[:5]
    refs = refs[5:]
    y_ref = refs.pop(0) if emit_primal else None
    yd_ref = refs.pop(0)
    acc_y = refs.pop(0) if emit_primal else None
    acc_yd = refs.pop(0) if has_xdot else None
    acc_u, acc_ud = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if emit_primal:
            acc_y[...] = jnp.zeros_like(acc_y)
        if has_xdot:
            acc_yd[...] = jnp.zeros_like(acc_yd)
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_ud[...] = jnp.zeros_like(acc_ud)

    x = x_ref[...]
    w = w_ref[...]
    a = a_ref[...]
    # one read of the x/W blocks feeds the primal AND every tangent
    if emit_primal:
        acc_y[...] += mxu_dot(x, w)
    acc_u[...] += mxu_dot(x, a)
    for t in range(n_t):  # static unroll over the tangent axis
        acc_ud[t] += mxu_dot(x, ad_ref[t])
        if has_xdot:
            xd_t = xd_ref[t]
            acc_yd[t] += mxu_dot(xd_t, w)
            acc_ud[t] += mxu_dot(xd_t, a)

    @pl.when(k == n_k - 1)
    def _finish():
        b = b_ref[...].astype(jnp.float32)
        u = acc_u[...]
        if emit_primal:
            y = acc_y[...] + scale * mxu_dot(u, b)
            y_ref[...] = y.astype(y_ref.dtype)
        for t in range(n_t):
            bd_t = bd_ref[t].astype(jnp.float32)
            yd = scale * (
                mxu_dot(acc_ud[t], b)
                + mxu_dot(u, bd_t))
            if has_xdot:
                yd = yd + acc_yd[t]
            yd_ref[t] = yd.astype(yd_ref.dtype)


def lora_dual_mt_kernel(x, xdots, w, a, adots, b, bdots, *, scale: float,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, interpret: bool,
                        emit_primal: bool = True):
    """x: (M,K); xdots: (T,M,K) or None; w: (K,N); a/adots: (K,r)/(T,K,r);
    b/bdots: (r,N)/(T,r,N) -> (y (M,N), ydots (T,M,N)), or just ydots when
    ``emit_primal=False`` (tangent-only pass — used by the AD dispatch rule,
    whose primal output must stay independent of tangents for
    jax.linearize's partial evaluation to split the two)."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    T = adots.shape[0]
    has_xdot = xdots is not None
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "caller (ops.py) must pad to block multiples")
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)

    kernel = functools.partial(_mt_kernel, scale=scale, n_k=n_k, n_t=T,
                               has_xdot=has_xdot, emit_primal=emit_primal)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),       # x
    ]
    operands = [x]
    if has_xdot:
        in_specs.append(
            pl.BlockSpec((T, block_m, block_k), lambda i, j, k: (0, i, k)))
        operands.append(xdots)
    in_specs += [
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),       # w
        pl.BlockSpec((block_k, r), lambda i, j, k: (k, 0)),             # a
        pl.BlockSpec((T, block_k, r), lambda i, j, k: (0, k, 0)),       # adots
        pl.BlockSpec((r, block_n), lambda i, j, k: (0, j)),             # b
        pl.BlockSpec((T, r, block_n), lambda i, j, k: (0, 0, j)),       # bdots
    ]
    operands += [w, a, adots, b, bdots]
    out_specs = [
        pl.BlockSpec((T, block_m, block_n), lambda i, j, k: (0, i, j)),
    ]
    out_shape = [jax.ShapeDtypeStruct((T, M, N), x.dtype)]
    # the (T, bm, bn) input-tangent accumulator is only allocated when xdots
    # exist — in the common first-perturbed-unit case it would hold zeros
    # while eating ~T*bm*bn*4B of VMEM per grid cell
    scratch = ([pltpu.VMEM((T, block_m, block_n), jnp.float32)]
               if has_xdot else [])
    scratch += [
        pltpu.VMEM((block_m, r), jnp.float32),
        pltpu.VMEM((T, block_m, r), jnp.float32),
    ]
    if emit_primal:
        out_specs.insert(
            0, pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)))
        out_shape.insert(0, jax.ShapeDtypeStruct((M, N), x.dtype))
        scratch.insert(0, pltpu.VMEM((block_m, block_n), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return outs if emit_primal else outs[0]


def _mt_jvps_kernel(*refs, scale: float, n_k: int, n_t: int, has_xdot: bool):
    """Contraction epilogue: per-(i, j) tile jvp partials <gy, ydot_t>
    without ever forming a ydot tile.

    The k-reduction reuses the mt accumulators (u / per-tangent udots); the
    frozen-weight term is contracted INCREMENTALLY — zw = gy @ w_kᵀ is
    computed once per k step (one frozen-W GEMM shared by all T tangents)
    and dotted against each xdot tile into a (T, 1) jvp-partial accumulator
    in VMEM — so neither a (T, bm, bn) tangent tile nor a (T, bm, bn)
    scratch ever exists. At the last k step the LoRA terms collapse to
    rank-r contractions (z1 = gy @ bᵀ against udots, z2 = uᵀ @ gy against
    bdots) and the (T, 1) per-block partials are written out — the only
    HBM the epilogue writes is one scalar per tangent per grid tile.
    """
    refs = list(refs)
    x_ref = refs.pop(0)
    xd_ref = refs.pop(0) if has_xdot else None
    w_ref, a_ref, ad_ref, b_ref, bd_ref, gy_ref = refs[:6]
    refs = refs[6:]
    out_ref = refs.pop(0)
    acc_u, acc_ud = refs[:2]
    acc_j = refs[2] if has_xdot else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_ud[...] = jnp.zeros_like(acc_ud)
        if has_xdot:
            acc_j[...] = jnp.zeros_like(acc_j)

    x = x_ref[...]
    a = a_ref[...]
    acc_u[...] += mxu_dot(x, a)
    if has_xdot:
        gy = gy_ref[...].astype(jnp.float32)
        # ONE frozen-weight GEMM per k step, shared across all T tangents:
        # <gy, xd_t @ w_k> = <gy @ w_kᵀ, xd_t>
        zw = mxu_dot(gy, w_ref[...].T)
    for t in range(n_t):  # static unroll over the tangent axis
        acc_ud[t] += mxu_dot(x, ad_ref[t])
        if has_xdot:
            xd_t = xd_ref[t]
            acc_ud[t] += mxu_dot(xd_t, a)
            acc_j[t:t + 1, :] += jnp.sum(zw * xd_t.astype(jnp.float32),
                                         keepdims=True)

    @pl.when(k == n_k - 1)
    def _finish():
        gy = gy_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        u = acc_u[...]
        z1 = mxu_dot(gy, b.T)                       # (bm, r)
        z2 = mxu_dot(u.T, gy)                       # (r, bn)
        for t in range(n_t):
            bd_t = bd_ref[t].astype(jnp.float32)
            part = scale * (jnp.sum(z1 * acc_ud[t], keepdims=True)
                            + jnp.sum(z2 * bd_t, keepdims=True))    # (1, 1)
            if has_xdot:
                part = part + acc_j[t:t + 1, :]
            out_ref[0, 0, t:t + 1, :] = part


def lora_dual_mt_jvps_kernel(x, xdots, w, a, adots, b, bdots, gy, *,
                             scale: float, block_m: int = 128,
                             block_n: int = 128, block_k: int = 128,
                             interpret: bool):
    """In-kernel fused jvp contraction: all T scalars <gy, ydot_t> with NO
    (T, M, N) tangent output — the HBM side of the epilogue is one (T,)
    partial per (i, j) grid tile, summed by the caller (ops.py).

    x: (M,K); xdots: (T,M,K) or None; w: (K,N); a/adots: (K,r)/(T,K,r);
    b/bdots: (r,N)/(T,r,N); gy: (M,N) -> per-block partials
    (M/bm, N/bn, T, 1) fp32."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    T = adots.shape[0]
    has_xdot = xdots is not None
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "caller (ops.py) must pad to block multiples")
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)

    kernel = functools.partial(_mt_jvps_kernel, scale=scale, n_k=n_k, n_t=T,
                               has_xdot=has_xdot)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),       # x
    ]
    operands = [x]
    if has_xdot:
        in_specs.append(
            pl.BlockSpec((T, block_m, block_k), lambda i, j, k: (0, i, k)))
        operands.append(xdots)
    in_specs += [
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),       # w
        pl.BlockSpec((block_k, r), lambda i, j, k: (k, 0)),             # a
        pl.BlockSpec((T, block_k, r), lambda i, j, k: (0, k, 0)),       # adots
        pl.BlockSpec((r, block_n), lambda i, j, k: (0, j)),             # b
        pl.BlockSpec((T, r, block_n), lambda i, j, k: (0, 0, j)),       # bdots
        pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),       # gy
    ]
    operands += [w, a, adots, b, bdots, gy]
    scratch = [
        pltpu.VMEM((block_m, r), jnp.float32),
        pltpu.VMEM((T, block_m, r), jnp.float32),
    ]
    if has_xdot:
        scratch.append(pltpu.VMEM((T, 1), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        # (T, 1) trailing dims span the whole array: a (1, T) block of a
        # (N/bn, T) array would break the TPU's (8, 128) block-tiling rule
        out_specs=pl.BlockSpec((1, 1, T, 1), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((M // block_m, N // block_n, T, 1),
                                       jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _multi_kernel(x_ref, idx_ref, w_ref, a_ref, b_ref, y_ref, acc_y, acc_u,
                  *, scale: float, n_k: int, n_pages: int):
    """Multi-adapter LoRA projection: each row of the x block carries an
    adapter-page index; all P resident pages' rank-r partial products
    accumulate in VMEM and the finish epilogue one-hot selects each row's
    page. ONE pass over the shared frozen W serves every adapter — the
    frozen GEMM (the overwhelming majority of FLOPs) is not re-read or
    recomputed per adapter, exactly the ``_mt`` idiom with pages in place
    of tangents."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_y[...] = jnp.zeros_like(acc_y)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]
    acc_y[...] += mxu_dot(x, w_ref[...])
    for p in range(n_pages):  # static unroll over resident adapter pages
        acc_u[p] += mxu_dot(x, a_ref[p])

    @pl.when(k == n_k - 1)
    def _finish():
        idx = idx_ref[...]                             # (bm, 1) int32
        lo = jnp.zeros_like(acc_y)
        for p in range(n_pages):
            bp = b_ref[p].astype(jnp.float32)
            yp = scale * mxu_dot(acc_u[p], bp)
            # adding the zero-masked other pages is exact (x + 0.0 == x):
            # no cross-adapter contamination, each row sees only its page
            lo = lo + jnp.where(idx == p, yp, 0.0)
        # both terms are rounded to the output dtype before the add, as the
        # single-adapter projection rounds them (x @ W + lora.astype(dtype))
        dt = y_ref.dtype
        y = (acc_y[...].astype(dt).astype(jnp.float32)
             + lo.astype(dt).astype(jnp.float32))
        y_ref[...] = y.astype(dt)


def lora_dual_multi_kernel(x, idx, w, a_stack, b_stack, *, scale: float,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128, interpret: bool):
    """x: (M,K); idx: (M,1) int32 adapter-page per row; w: (K,N);
    a_stack: (P,K,r); b_stack: (P,r,N) -> y (M,N).

    Grid and accumulator layout mirror ``lora_dual_mt_kernel`` with the
    page axis P where the tangent axis T was: the (P, bm, r) rank-r
    partials live in VMEM across the K reduction, and the frozen-W GEMM
    runs once for the whole heterogeneous batch. P is the resident-page
    count of the serving adapter cache (small, ≤ batch)."""
    M, K = x.shape
    N = w.shape[1]
    r = a_stack.shape[2]
    P = a_stack.shape[0]
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "caller (ops.py) must pad to block multiples")
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)

    kernel = functools.partial(_multi_kernel, scale=scale, n_k=n_k,
                               n_pages=P)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),       # x
        pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),             # idx
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),       # w
        pl.BlockSpec((P, block_k, r), lambda i, j, k: (0, k, 0)),       # A
        pl.BlockSpec((P, r, block_n), lambda i, j, k: (0, 0, j)),       # B
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((P, block_m, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, idx, w, a_stack, b_stack)


def lora_dual_kernel(x, xdot, w, a, adot, b, bdot, *, scale: float,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 128, interpret: bool):
    """Single-tangent compatibility wrapper: T=1 slice of the mt kernel.

    x/xdot: (M,K); w: (K,N); a/adot: (K,r); b/bdot: (r,N) -> (y, ydot)."""
    y, ydots = lora_dual_mt_kernel(
        x, xdot[None], w, a, adot[None], b, bdot[None], scale=scale,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)
    return y, ydots[0]
