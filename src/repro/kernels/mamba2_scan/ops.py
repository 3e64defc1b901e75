"""jit'd wrappers: (B,S,H,hd) <-> (B*H, S, hd) layout + padding of S.

``mamba2_scan``             single-pass primal
``mamba2_scan_mt``          multi-tangent fused pass (y, ydots (T, ...)) —
                            one walk of the primal state serves all T
                            tangents
``mamba2_scan_mt_tangents`` tangent-only variant (the AD dispatch route;
                            its primal output must come from the jnp mirror
                            so jax.linearize can split the custom-JVP rule)
``mamba2_scan_mt_jvps``     fused contraction epilogue: all T scalars
                            <gy, ydot_t> — per-token ydots are contracted
                            against gy inside the kernel and never written
                            to HBM (the cotangent-known estimator route)

Tangent-axis contract: tangents carry a leading T axis — xdtds is
(T, B, S, H, hd), bds/cds are (T, B, S, N), decayds is (T, B, S, H);
ydots come back as (T, B, S, H, hd). B/C streams stay at their (B, S, N)
width end-to-end (the per-head fold happens inside the kernel grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import sum_partials
from repro.kernels.mamba2_scan.kernel import (
    mamba2_scan_kernel,
    mamba2_scan_mt_jvps_kernel,
    mamba2_scan_mt_kernel,
)


def _layout(xdt, bmat, cmat, decay, block_s):
    """(B,S,H,hd)->(BH,S,hd) flattening + S padding for the primal operands.
    Padded steps keep the state intact (decay=1, xdt=0); padded y rows are
    dropped."""
    B, S, H, hd = xdt.shape
    bs = min(block_s, S)
    pad = (-S) % bs

    xb = xdt.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    db = decay.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, S)
    bb = bmat.astype(jnp.float32)
    cb = cmat.astype(jnp.float32)
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0)))
        db = jnp.pad(db, ((0, 0), (0, pad)), constant_values=1.0)
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0)))
        cb = jnp.pad(cb, ((0, 0), (0, pad), (0, 0)))
    return (xb, bb, cb, db), (B, S, H, hd, bs, pad)


def _layout_t(xdtds, bds, cds, decayds, T, B, S, H, hd, pad):
    """Tangent-stack flattening; padded tangent steps are zero (decayd=0,
    xdtd=0, Bd=Cd=0) so every tangent state is preserved too."""
    xdb = xdtds.astype(jnp.float32).transpose(0, 1, 3, 2, 4).reshape(
        T, B * H, S, hd)
    ddb = decayds.astype(jnp.float32).transpose(0, 1, 3, 2).reshape(
        T, B * H, S)
    bdb = bds.astype(jnp.float32)
    cdb = cds.astype(jnp.float32)
    if pad:
        xdb = jnp.pad(xdb, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ddb = jnp.pad(ddb, ((0, 0), (0, 0), (0, pad)))
        bdb = jnp.pad(bdb, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cdb = jnp.pad(cdb, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return xdb, bdb, cdb, ddb


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def mamba2_scan(xdt, bmat, cmat, decay, block_s: int = 64, *,
                interpret: bool):
    """xdt: (B,S,H,hd); bmat,cmat: (B,S,N); decay: (B,S,H). Returns
    y (B,S,H,hd) fp32. Fresh state per call (training semantics); the
    decode path keeps its state outside and uses the jnp reference."""
    (xb, bb, cb, db), (B, S, H, hd, bs, pad) = _layout(
        xdt, bmat, cmat, decay, block_s)
    y = mamba2_scan_kernel(xb, bb, cb, db, n_heads=H, block_s=bs,
                           interpret=interpret)
    return y[:, :S].reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def mamba2_scan_mt(xdt, bmat, cmat, decay, xdtds, bds, cds, decayds,
                   block_s: int = 64, *, interpret: bool):
    """Multi-tangent fused pass -> (y (B,S,H,hd), ydots (T,B,S,H,hd))."""
    T = xdtds.shape[0]
    (xb, bb, cb, db), (B, S, H, hd, bs, pad) = _layout(
        xdt, bmat, cmat, decay, block_s)
    xdb, bdb, cdb, ddb = _layout_t(xdtds, bds, cds, decayds, T, B, S, H, hd,
                                   pad)
    y, yds = mamba2_scan_mt_kernel(xb, bb, cb, db, xdb, bdb, cdb, ddb,
                                   n_heads=H, block_s=bs, interpret=interpret)
    y = y[:, :S].reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    yds = yds[:, :, :S].reshape(T, B, H, S, hd).transpose(0, 1, 3, 2, 4)
    return y, yds


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def mamba2_scan_mt_tangents(xdt, bmat, cmat, decay, xdtds, bds, cds, decayds,
                            block_s: int = 64, *, interpret: bool):
    """Tangent-only fused pass -> ydots (T,B,S,H,hd). Same contract as
    ``mamba2_scan_mt`` but skips the primal y output (the primal state walk
    still runs in-kernel — the tangent recurrence needs h_{t-1})."""
    T = xdtds.shape[0]
    (xb, bb, cb, db), (B, S, H, hd, bs, pad) = _layout(
        xdt, bmat, cmat, decay, block_s)
    xdb, bdb, cdb, ddb = _layout_t(xdtds, bds, cds, decayds, T, B, S, H, hd,
                                   pad)
    yds = mamba2_scan_mt_kernel(xb, bb, cb, db, xdb, bdb, cdb, ddb,
                                n_heads=H, block_s=bs, interpret=interpret,
                                emit_primal=False)
    return yds[:, :, :S].reshape(T, B, H, S, hd).transpose(0, 1, 3, 2, 4)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def mamba2_scan_mt_jvps(xdt, bmat, cmat, decay, xdtds, bds, cds, decayds, gy,
                        block_s: int = 64, *, interpret: bool):
    """Fused jvp-contraction epilogue -> jvps (T,) fp32 = <gy, ydot_t>.

    Same operand contract as ``mamba2_scan_mt`` plus the output cotangent
    gy: (B,S,H,hd); the T tangent outputs are contracted inside the kernel
    and never reach HBM (only (BH, T) per-row partials do)."""
    T = xdtds.shape[0]
    (xb, bb, cb, db), (B, S, H, hd, bs, pad) = _layout(
        xdt, bmat, cmat, decay, block_s)
    xdb, bdb, cdb, ddb = _layout_t(xdtds, bds, cds, decayds, T, B, S, H, hd,
                                   pad)
    # zero-padded gy rows contribute exactly 0 to every partial
    gyb = gy.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    if pad:
        gyb = jnp.pad(gyb, ((0, 0), (0, pad), (0, 0)))
    parts = mamba2_scan_mt_jvps_kernel(xb, bb, cb, db, xdb, bdb, cdb, ddb,
                                       gyb, n_heads=H, block_s=bs,
                                       interpret=interpret)
    return sum_partials(parts)
