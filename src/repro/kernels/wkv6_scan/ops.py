"""jit'd wrappers: (B,S,H,hd) <-> (B*H, S, hd) layout + padding of S.

``wkv6_scan``             single-pass primal
``wkv6_scan_mt``          multi-tangent fused pass (y, ydots (T, ...)) — one
                          walk of the primal state serves all T tangents
``wkv6_scan_mt_tangents`` tangent-only variant (the AD dispatch route; its
                          primal output must come from the jnp mirror so
                          jax.linearize can split the custom-JVP rule)
``wkv6_scan_mt_jvps``     fused contraction epilogue: all T scalars
                          <gy, ydot_t> — per-token ydots are contracted
                          against gy inside the kernel and never written to
                          HBM (the cotangent-known estimator route)

Tangent-axis contract: tangents carry a leading T axis — rds/kds/vds/wds are
(T, B, S, H, hd) and uds (when the per-head bonus u carries a tangent) is
(T, H, hd); ydots come back as (T, B, S, H, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import sum_partials
from repro.kernels.wkv6_scan.kernel import (
    wkv6_scan_kernel,
    wkv6_scan_mt_jvps_kernel,
    wkv6_scan_mt_kernel,
)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan(r, k, v, w, u, block_s: int = 64, *, interpret: bool):
    """r,k,v,w: (B,S,H,hd); u: (H,hd). Returns y (B,S,H,hd) fp32.

    Fresh state per call (training semantics); the decode path keeps its
    state outside and uses the jnp reference for single steps.
    """
    B, S, H, hd = r.shape
    bs = min(block_s, S)
    pad = (-S) % bs

    def to_bh(t):
        t = t.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return t

    # pad w with ones (decay) so padded steps keep state intact — irrelevant
    # anyway since padded y rows are dropped
    rb, kb, vb = to_bh(r), to_bh(k), to_bh(v)
    wb = to_bh(w)
    if pad:
        wb = wb.at[:, S:, :].set(1.0)
    ub = jnp.broadcast_to(u.astype(jnp.float32)[None], (B, H, hd)).reshape(B * H, hd)
    y = wkv6_scan_kernel(rb, kb, vb, wb, ub, block_s=bs, interpret=interpret)
    y = y[:, :S].reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return y


def _mt_layout(r, k, v, w, u, rds, kds, vds, wds, uds, block_s):
    """Shared (B,S,H,hd)->(BH,S,hd) flattening + S padding for the mt entry
    points. Padded steps keep both the primal state (w=1, kv=0) and every
    tangent state (wd=0, kvd=0) intact; padded y/ydot rows are dropped."""
    B, S, H, hd = r.shape
    T = rds.shape[0]
    bs = min(block_s, S)
    pad = (-S) % bs

    def to_bh(t):
        t = t.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return t

    def to_bh_t(t):
        t = t.astype(jnp.float32).transpose(0, 1, 3, 2, 4).reshape(
            T, B * H, S, hd)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return t

    rb, kb, vb, wb = to_bh(r), to_bh(k), to_bh(v), to_bh(w)
    if pad:
        wb = wb.at[:, S:, :].set(1.0)
    rdb, kdb, vdb, wdb = to_bh_t(rds), to_bh_t(kds), to_bh_t(vds), to_bh_t(wds)
    ub = jnp.broadcast_to(u.astype(jnp.float32)[None],
                          (B, H, hd)).reshape(B * H, hd)
    udb = None
    if uds is not None:
        udb = jnp.broadcast_to(uds.astype(jnp.float32)[:, None],
                               (T, B, H, hd)).reshape(T, B * H, hd)
    return (rb, kb, vb, wb, ub, rdb, kdb, vdb, wdb, udb), (B, S, H, hd, T, bs)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt(r, k, v, w, u, rds, kds, vds, wds, uds=None,
                 block_s: int = 64, *, interpret: bool):
    """Multi-tangent fused pass. r,k,v,w: (B,S,H,hd); u: (H,hd); tangents
    (T,B,S,H,hd) (+ uds (T,H,hd) or None). Returns (y, ydots) fp32."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    y, yds = wkv6_scan_mt_kernel(*[o for o in ops if o is not None],
                                 block_s=bs, interpret=interpret)
    y = y[:, :S].reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    yds = yds[:, :, :S].reshape(T, B, H, S, hd).transpose(0, 1, 3, 2, 4)
    return y, yds


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt_tangents(r, k, v, w, u, rds, kds, vds, wds, uds=None,
                          block_s: int = 64, *, interpret: bool):
    """Tangent-only fused pass -> ydots (T,B,S,H,hd). Same contract as
    ``wkv6_scan_mt`` but skips the primal y output (the primal state walk
    still runs in-kernel — the tangent recurrence needs S_{t-1})."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    yds = wkv6_scan_mt_kernel(*[o for o in ops if o is not None],
                              block_s=bs, interpret=interpret,
                              emit_primal=False)
    return yds[:, :, :S].reshape(T, B, H, S, hd).transpose(0, 1, 3, 2, 4)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt_jvps(r, k, v, w, u, rds, kds, vds, wds, gy, uds=None,
                      block_s: int = 64, *, interpret: bool):
    """Fused jvp-contraction epilogue -> jvps (T,) fp32 = <gy, ydot_t>.

    Same operand contract as ``wkv6_scan_mt`` plus the output cotangent
    gy: (B,S,H,hd); the T tangent outputs are contracted inside the kernel
    and never reach HBM (only (BH, T) per-row partials do)."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    rb, kb, vb, wb, ub, rdb, kdb, vdb, wdb, udb = ops
    # zero-padded gy rows contribute exactly 0 to every partial
    gyb = gy.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    pad = (-S) % bs
    if pad:
        gyb = jnp.pad(gyb, ((0, 0), (0, pad), (0, 0)))
    parts = wkv6_scan_mt_jvps_kernel(rb, kb, vb, wb, ub, rdb, kdb, vdb, wdb,
                                     gyb, udb, block_s=bs,
                                     interpret=interpret)
    return sum_partials(parts)
