"""jit'd wrappers: (B,H,S,hd) <-> (BH,S,hd) reshape and padding of hd to the
lane width.

GQA: k/v stay at their (B,KV,S,hd) width end-to-end — query-head blocks are
mapped to their KV head inside the kernel grid (contiguous groups, matching
``models/attention.py``), so the H/KV× repeated-K/V HBM blowup of the old
``jnp.repeat`` pre-pass never materializes.

``force_pad_hd`` pads hd to a multiple of 128 lanes even under
``interpret=True`` so the CPU oracle exercises the exact padded-lane
dataflow that runs on real TPUs (zero-padded lanes don't affect scores —
both q and k are padded — and the softmax scale keeps the original hd).

``swa_attention_mt`` / ``swa_attention_mt_tangents``: tangents carry a
leading T axis ((T,B,H,S,hd) for qds, (T,B,KV,S,hd) for kds/vds); one pass
over the primal q/k/v produces out plus all T outdots.

``swa_attention_mt_jvps``: fused contraction epilogue — all T scalars
<gy, outd_t> (gy: (B,H,S,hd)); the tangent outputs are contracted against
gy inside the kernel and never written to HBM (cotangent-known estimator
route).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import sum_partials
from repro.kernels.swa_attention.kernel import (
    swa_attention_kernel,
    swa_attention_mt_jvps_kernel,
    swa_attention_mt_kernel,
)


def _pad_plan(hd, interpret, force_pad_hd):
    return (-hd) % 128 if (not interpret or force_pad_hd) else 0


def _block_plan(S, block_q, block_k):
    """Clamp blocks to S and pick the S padding. When neither clamped block
    divides the other (e.g. S=100 clamps bq to 100 over bk=64), their lcm
    would explode the padding — clamp both to the smaller block instead, so
    the pad is always < max(bq, bk)."""
    bq, bk = min(block_q, S), min(block_k, S)
    if math.lcm(bq, bk) > max(bq, bk):
        bq = bk = min(bq, bk)
    return bq, bk, (-S) % math.lcm(bq, bk)


def _pad_last(t, pad_hd):
    if not pad_hd:
        return t
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, pad_hd),))


def _pad_seq(t, pad_s):
    """Zero-pad S (axis -2). Padded queries are dropped after the call;
    padded keys sit at positions >= S so the causal mask (k_pos <= q_pos)
    never lets a real query attend them."""
    if not pad_s:
        return t
    widths = ((0, 0),) * (t.ndim - 2) + ((0, pad_s), (0, 0))
    return jnp.pad(t, widths)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret", "force_pad_hd"))
def swa_attention(q, k, v, window=None, block_q=128, block_k=128,
                  *, interpret: bool, force_pad_hd=False):
    """q: (B,H,S,hd); k,v: (B,KV,S,hd) with H % KV == 0. Causal SWA."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    bq, bk, pad_s = _block_plan(S, block_q, block_k)
    Sp = S + pad_s
    pad_hd = _pad_plan(hd, interpret, force_pad_hd)
    q, k, v = (_pad_last(_pad_seq(t, pad_s), pad_hd) for t in (q, k, v))
    out = swa_attention_kernel(
        q.reshape(B * H, Sp, hd + pad_hd),
        k.reshape(B * KV, Sp, hd + pad_hd),
        v.reshape(B * KV, Sp, hd + pad_hd),
        window=window, block_q=bq, block_k=bk, interpret=interpret,
        scale=1.0 / float(hd) ** 0.5, n_heads=H, kv_groups=H // KV)
    out = out.reshape(B, H, Sp, hd + pad_hd)
    return out[:, :, :S, :hd]


def _mt_layout(q, k, v, qds, kds, vds, pad_hd, pad_s):
    B, H, S, hd = q.shape
    KV = k.shape[1]
    T = qds.shape[0]
    hp = hd + pad_hd
    Sp = S + pad_s
    q, k, v, qds, kds, vds = (_pad_last(_pad_seq(t, pad_s), pad_hd)
                              for t in (q, k, v, qds, kds, vds))
    return (q.reshape(B * H, Sp, hp), k.reshape(B * KV, Sp, hp),
            v.reshape(B * KV, Sp, hp), qds.reshape(T, B * H, Sp, hp),
            kds.reshape(T, B * KV, Sp, hp), vds.reshape(T, B * KV, Sp, hp),
            (B, H, KV, S, hd, T))


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret", "force_pad_hd"))
def swa_attention_mt(q, k, v, qds, kds, vds, window=None, block_q=128,
                     block_k=128, *, interpret: bool, force_pad_hd=False):
    """Multi-tangent fused pass -> (out (B,H,S,hd), outds (T,B,H,S,hd))."""
    bq, bk, pad_s = _block_plan(q.shape[-2], block_q, block_k)
    pad_hd = _pad_plan(q.shape[-1], interpret, force_pad_hd)
    qb, kb, vb, qdb, kdb, vdb, (B, H, KV, S, hd, T) = _mt_layout(
        q, k, v, qds, kds, vds, pad_hd, pad_s)
    out, outds = swa_attention_mt_kernel(
        qb, kb, vb, qdb, kdb, vdb, window=window, block_q=bq,
        block_k=bk, interpret=interpret,
        scale=1.0 / float(hd) ** 0.5, n_heads=H, kv_groups=H // KV)
    out = out.reshape(B, H, S + pad_s, hd + pad_hd)[:, :, :S, :hd]
    outds = outds.reshape(T, B, H, S + pad_s, hd + pad_hd)[..., :S, :hd]
    return out, outds


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret", "force_pad_hd"))
def swa_attention_mt_tangents(q, k, v, qds, kds, vds, window=None,
                              block_q=128, block_k=128, *, interpret: bool,
                              force_pad_hd=False):
    """Tangent-only fused pass -> outds (T,B,H,S,hd). Same contract as
    ``swa_attention_mt`` but skips the primal output (the AD dispatch rule
    keeps its primal a pure function of primal inputs for jax.linearize)."""
    bq, bk, pad_s = _block_plan(q.shape[-2], block_q, block_k)
    pad_hd = _pad_plan(q.shape[-1], interpret, force_pad_hd)
    qb, kb, vb, qdb, kdb, vdb, (B, H, KV, S, hd, T) = _mt_layout(
        q, k, v, qds, kds, vds, pad_hd, pad_s)
    outds = swa_attention_mt_kernel(
        qb, kb, vb, qdb, kdb, vdb, window=window, block_q=bq,
        block_k=bk, interpret=interpret,
        scale=1.0 / float(hd) ** 0.5, n_heads=H, kv_groups=H // KV,
        emit_primal=False)
    return outds.reshape(T, B, H, S + pad_s, hd + pad_hd)[..., :S, :hd]


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret", "force_pad_hd"))
def swa_attention_mt_jvps(q, k, v, qds, kds, vds, gy, window=None,
                          block_q=128, block_k=128, *, interpret: bool,
                          force_pad_hd=False):
    """Fused jvp-contraction epilogue -> jvps (T,) fp32 = <gy, outd_t>.

    Same operand contract as ``swa_attention_mt`` plus the output cotangent
    gy: (B,H,S,hd); the T tangent outputs are contracted inside the kernel
    and never reach HBM (only (B*H, S/bq, T) per-block partials do).
    Zero-padded gy rows/lanes contribute exactly 0 to every partial."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    bq, bk, pad_s = _block_plan(S, block_q, block_k)
    pad_hd = _pad_plan(hd, interpret, force_pad_hd)
    qb, kb, vb, qdb, kdb, vdb, (B, H, KV, S, hd, T) = _mt_layout(
        q, k, v, qds, kds, vds, pad_hd, pad_s)
    gyb = _pad_last(_pad_seq(gy, pad_s), pad_hd).reshape(
        B * H, S + pad_s, hd + pad_hd)
    parts = swa_attention_mt_jvps_kernel(
        qb, kb, vb, qdb, kdb, vdb, gyb, window=window, block_q=bq,
        block_k=bk, interpret=interpret,
        scale=1.0 / float(hd) ** 0.5, n_heads=H, kv_groups=H // KV)
    return sum_partials(parts)
