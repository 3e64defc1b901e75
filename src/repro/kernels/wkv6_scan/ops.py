"""jit'd wrappers: (B,S,H,hd) -> the kernels' (B, S, H*hd) layout (a free
reshape) + padding of S to whole grid blocks of whole sub-chunks
(``kernel.CHUNK``).

``wkv6_scan``             single-pass primal
``wkv6_scan_mt``          multi-tangent fused pass (y, ydots (T, ...)) — each
                          sub-chunk's primal pieces serve all T tangents
``wkv6_scan_mt_tangents`` tangent-only variant (the AD dispatch route; its
                          primal output must come from the jnp mirror so
                          jax.linearize can split the custom-JVP rule)
``wkv6_scan_mt_jvps``     fused contraction epilogue: all T scalars
                          <gy, ydot_t> — ydots are contracted against gy
                          inside the kernel and never written to HBM (the
                          cotangent-known estimator route)

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
    CHUNK,
    wkv6_scan_kernel,
    wkv6_scan_mt_jvps_kernel,
    wkv6_scan_mt_kernel,
)


def _block(block_s, S):
    """Tokens per grid step: ``block_s``, or S if shorter, rounded up to
    whole sub-chunks; S is padded to a multiple of it."""
    return -(-min(block_s, S) // CHUNK) * CHUNK


def _flat(x, pad, fill=0.0):
    """(..., S, H, hd) -> (..., S + pad, H*hd) fp32: a free reshape to the
    kernels' layout, padded along the sequence with ``fill``."""
    x = x.astype(jnp.float32)
    x = x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[-2] = (0, pad)
        x = jnp.pad(x, widths, constant_values=fill)
    return x


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan(r, k, v, w, u, block_s: int = 64, *, interpret: bool):
    """r,k,v,w: (B,S,H,hd); u: (H,hd). Returns y (B,S,H,hd) fp32.

    Fresh state per call (training semantics); the decode path keeps its
    state outside and uses the jnp reference for single steps.
    """
    B, S, H, hd = r.shape
    bs = _block(block_s, S)
    pad = (-S) % bs
    # pad w with ones (decay) so padded steps keep state intact — irrelevant
    # anyway since padded y rows are dropped (padding only ever trails)
    y = wkv6_scan_kernel(_flat(r, pad), _flat(k, pad), _flat(v, pad),
                         _flat(w, pad, 1.0), u.astype(jnp.float32).reshape(
                             1, H * hd), hd=hd, block_s=bs,
                         interpret=interpret)
    return y[:, :S].reshape(B, S, H, hd)


def _mt_layout(r, k, v, w, u, rds, kds, vds, wds, uds, block_s):
    """Shared flattening + S padding for the mt entry points. Padded steps
    keep both the primal state (w=1, kv=0) and every tangent state (wd=0,
    kvd=0) intact; padded y/ydot rows are dropped."""
    B, S, H, hd = r.shape
    T = rds.shape[0]
    bs = _block(block_s, S)
    pad = (-S) % bs
    ops = [_flat(t, pad) for t in (r, k, v)] + [_flat(w, pad, 1.0)]
    ops.append(u.astype(jnp.float32).reshape(1, H * hd))
    ops += [_flat(t, pad) for t in (rds, kds, vds, wds)]
    ops.append(None if uds is None
               else uds.astype(jnp.float32).reshape(T, 1, H * hd))
    return ops, (B, S, H, hd, T, bs)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt(r, k, v, w, u, rds, kds, vds, wds, uds=None,
                 block_s: int = 64, *, interpret: bool):
    """Multi-tangent fused pass. r,k,v,w: (B,S,H,hd); u: (H,hd); tangents
    (T,B,S,H,hd) (+ uds (T,H,hd) or None). Returns (y, ydots) fp32."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    y, yds = wkv6_scan_mt_kernel(*ops, hd=hd, block_s=bs,
                                 interpret=interpret)
    return (y[:, :S].reshape(B, S, H, hd),
            yds[:, :, :S].reshape(T, B, S, H, hd))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt_tangents(r, k, v, w, u, rds, kds, vds, wds, uds=None,
                          block_s: int = 64, *, interpret: bool):
    """Tangent-only fused pass -> ydots (T,B,S,H,hd). Same contract as
    ``wkv6_scan_mt`` but skips the primal y output (the primal pieces are
    still computed in-kernel — the tangents need them and the state)."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    yds = wkv6_scan_mt_kernel(*ops, hd=hd, block_s=bs, interpret=interpret,
                              emit_primal=False)
    return yds[:, :, :S].reshape(T, B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def wkv6_scan_mt_jvps(r, k, v, w, u, rds, kds, vds, wds, gy, uds=None,
                      block_s: int = 64, *, interpret: bool):
    """Fused jvp-contraction epilogue -> jvps (T,) fp32 = <gy, ydot_t>.

    Same operand contract as ``wkv6_scan_mt`` plus the output cotangent
    gy: (B,S,H,hd); the T tangent outputs are contracted inside the kernel
    and never reach HBM (only (B, H/G, T) partials do)."""
    ops, (B, S, H, hd, T, bs) = _mt_layout(r, k, v, w, u, rds, kds, vds, wds,
                                           uds, block_s)
    *ops, udb = ops
    # zero-padded gy rows contribute exactly 0 to every partial
    parts = wkv6_scan_mt_jvps_kernel(*ops, _flat(gy, (-S) % bs), udb, hd=hd,
                                     block_s=bs, interpret=interpret)
    return sum_partials(parts)
