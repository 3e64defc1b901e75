"""Sliding-window flash attention — Pallas TPU kernel.

Online-softmax over key blocks restricted to the causal band
(q-window, q]. Only ceil((window+block_q)/block_k)+ key blocks are visited
per query block, so HBM traffic and FLOPs are linear in S for SWA layers
(gemma3 local layers, h2o-danube, zamba2's shared attention block).

GQA: q rows are (B*H) while k/v rows stay (B*KV) — the kv BlockSpec index
maps flatten the query head back to its KV group (contiguous groups, head h
reads kv head h // (H//KV)), so repeated K/V are NEVER materialized in HBM.

Grid: (B*H, S/block_q, n_kv_blocks) — kv innermost sequential; the running
max/denominator/accumulator live in VMEM scratch across kv steps.

The multi-tangent variant (``swa_attention_mt_kernel``) pushes T stacked
jvp tangents through the same online-softmax walk: per tangent it carries

    mu_d  = Σ_j e_j sd_j            (softmax-correction numerator)
    acc_d = Σ_j e_j (sd_j v_j + vd_j)

(e_j the unnormalized weights, sd the score tangent), rescaled by the same
alpha as the primal accumulator on every running-max update, and finishes

    outd = acc_d / l - (mu_d / l) * out.

One pass over the primal q/k/v serves all T tangents — the §5.3
"column-by-column jvp" cost collapses into per-tangent VPU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_dot


NEG_INF = -1e30


def _kv_block_index(qi, kv_step, *, block_q, block_k, window, n_k_total,
                    banded):
    """Index of the kv block visited at (query block qi, step kv_step).

    Banded mode: the first visited block covers position qs - window + 1
    (clamped to 0); out-of-band loads are clamped and masked away in-kernel.
    Non-banded mode (full causal, or window so wide the band covers all
    blocks): sweep blocks 0..n_k_total-1.
    """
    if not banded:
        return kv_step
    q_start = qi * block_q
    first = (q_start - (window - 1)) // block_k
    idx = first + kv_step
    return jnp.clip(idx, 0, n_k_total - 1)


def _kv_head_index(bh, *, n_heads, kv_groups):
    """Flat kv row for flat query row ``bh``: head h of H reads kv head
    h // kv_groups (contiguous groups — models/attention.py convention)."""
    if kv_groups == 1:
        return bh
    return (bh // n_heads) * (n_heads // kv_groups) + (bh % n_heads) // kv_groups


def _keep_mask(qi, step, *, block_q, block_k, window, n_k_total, banded):
    """(block_q, block_k) bool mask of valid (q, k) pairs for this step."""
    kv_idx = _kv_block_index(qi, step, block_q=block_q, block_k=block_k,
                             window=window, n_k_total=n_k_total, banded=banded)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = k_pos <= q_pos
    if window is not None:
        keep = keep & (k_pos > q_pos - window)
    if banded:
        # out-of-range steps are clamped by the index_map and would re-visit
        # an edge block — mask those visits out entirely
        q_start = qi * block_q
        raw_idx = (q_start - (window - 1)) // block_k + step
        keep = keep & (raw_idx >= 0) & (raw_idx < n_k_total)
    return keep


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_q, block_k, window, n_kv_steps, n_k_total, scale,
            banded):
    qi = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                       # (block_q, hd)
    k = k_ref[0]                                       # (block_k, hd)
    v = v_ref[0]

    keep = _keep_mask(qi, step, block_q=block_q, block_k=block_k,
                      window=window, n_k_total=n_k_total, banded=banded)

    s = mxu_dot(q, k.T) * scale
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_scr[...]                                # (block_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit keep-gating: exp(NEG_INF - NEG_INF) would be 1, not 0
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + mxu_dot(
        p.astype(v.dtype), v)
    m_scr[...] = m_new

    @pl.when(step == n_kv_steps - 1)
    def _finish():
        out = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)
        o_ref[...] = out[None]


def _plan(S, hd, window, block_q, block_k, scale):
    n_k_total = S // block_k
    if window is not None:
        # band spans floor((qs-W+1)/bk) .. floor((qs+bq-1)/bk) inclusive;
        # worst-case count over alignments:
        n_kv_steps = (window - 1 + block_q - 1) // block_k + 2
    else:
        n_kv_steps = n_k_total
    banded = window is not None and n_kv_steps < n_k_total
    if not banded:
        n_kv_steps = n_k_total
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    return n_k_total, n_kv_steps, banded, scale


def swa_attention_kernel(q, k, v, *, window, block_q=128, block_k=128,
                         interpret: bool, scale=None, n_heads=None,
                         kv_groups=1):
    """q: (B*H, S, hd); k,v: (B*KV, S, hd) -> out (B*H, S, hd). Causal;
    window may be None. ``scale`` overrides 1/sqrt(hd) (needed when hd was
    zero-padded). GQA (KV < H): pass ``n_heads=H`` and
    ``kv_groups=H // KV`` — kv blocks are indexed per query-head group
    in-grid, never repeated in HBM."""
    BH, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0
    n_heads = BH if n_heads is None else n_heads
    n_k_total, n_kv_steps, banded, scale = _plan(S, hd, window, block_q,
                                                 block_k, scale)

    grid = (BH, S // block_q, n_kv_steps)
    kv_map = functools.partial(_kv_block_index, block_q=block_q,
                               block_k=block_k, window=window,
                               n_k_total=n_k_total, banded=banded)
    kv_head = functools.partial(_kv_head_index, n_heads=n_heads,
                                kv_groups=kv_groups)
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               window=window, n_kv_steps=n_kv_steps,
                               n_k_total=n_k_total, scale=scale,
                               banded=banded)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, s: (kv_head(b), kv_map(i, s), 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, s: (kv_head(b), kv_map(i, s), 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _mt_kernel(q_ref, k_ref, v_ref, qd_ref, kd_ref, vd_ref, *rest,
               block_q, block_k, window, n_kv_steps, n_k_total, scale,
               banded, n_t, emit_primal):
    rest = list(rest)
    o_ref = rest.pop(0) if emit_primal else None
    od_ref = rest.pop(0)
    m_scr, l_scr, acc_scr, mu_d_scr, acc_d_scr = rest
    qi = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        mu_d_scr[...] = jnp.zeros_like(mu_d_scr)
        acc_d_scr[...] = jnp.zeros_like(acc_d_scr)

    q = q_ref[0]                                       # (block_q, hd)
    k = k_ref[0]                                       # (block_k, hd)
    v = v_ref[0]

    keep = _keep_mask(qi, step, block_q=block_q, block_k=block_k,
                      window=window, n_k_total=n_k_total, banded=banded)

    s = mxu_dot(q, k.T) * scale
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_scr[...]                                # (block_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + mxu_dot(
        p.astype(v.dtype), v)
    m_scr[...] = m_new

    for tau in range(n_t):                             # static unroll over T
        qd = qd_ref[tau, 0]
        kd = kd_ref[tau, 0]
        vd = vd_ref[tau, 0]
        # score tangent; p==0 lanes kill any out-of-band sd values
        sd = (mxu_dot(qd, k.T) + mxu_dot(q, kd.T)) * scale
        psd = p * sd
        mu_d_scr[tau] = mu_d_scr[tau] * alpha + psd.sum(axis=-1, keepdims=True)
        acc_d_scr[tau] = acc_d_scr[tau] * alpha + (
            mxu_dot(psd.astype(v.dtype), v)
            + mxu_dot(p.astype(vd.dtype), vd))

    @pl.when(step == n_kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        out = acc_scr[...] / l
        if emit_primal:
            o_ref[...] = out.astype(o_ref.dtype)[None]
        for tau in range(n_t):
            outd = acc_d_scr[tau] / l - (mu_d_scr[tau] / l) * out
            od_ref[tau] = outd.astype(od_ref.dtype)[None]


def _mt_jvps_kernel(q_ref, k_ref, v_ref, qd_ref, kd_ref, vd_ref, gy_ref,
                    out_ref, m_scr, l_scr, acc_scr, mu_d_scr, acc_d_scr,
                    *, block_q, block_k, window, n_kv_steps, n_k_total,
                    scale, banded, n_t):
    """Contraction epilogue: the same online-softmax walk (primal + T
    tangent accumulators) as ``_mt_kernel``, but the per-query-block outd_t
    tiles are contracted against the incoming gy tile at the final kv step
    instead of being written out — only (T, 1) per-block partials reach
    HBM."""
    qi = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        mu_d_scr[...] = jnp.zeros_like(mu_d_scr)
        acc_d_scr[...] = jnp.zeros_like(acc_d_scr)

    q = q_ref[0]                                       # (block_q, hd)
    k = k_ref[0]                                       # (block_k, hd)
    v = v_ref[0]

    keep = _keep_mask(qi, step, block_q=block_q, block_k=block_k,
                      window=window, n_k_total=n_k_total, banded=banded)

    s = mxu_dot(q, k.T) * scale
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_scr[...]                                # (block_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + mxu_dot(
        p.astype(v.dtype), v)
    m_scr[...] = m_new

    for tau in range(n_t):                             # static unroll over T
        qd = qd_ref[tau, 0]
        kd = kd_ref[tau, 0]
        vd = vd_ref[tau, 0]
        sd = (mxu_dot(qd, k.T) + mxu_dot(q, kd.T)) * scale
        psd = p * sd
        mu_d_scr[tau] = mu_d_scr[tau] * alpha + psd.sum(axis=-1, keepdims=True)
        acc_d_scr[tau] = acc_d_scr[tau] * alpha + (
            mxu_dot(psd.astype(v.dtype), v)
            + mxu_dot(p.astype(vd.dtype), vd))

    @pl.when(step == n_kv_steps - 1)
    def _finish():
        gy = gy_ref[0].astype(jnp.float32)             # (block_q, hd)
        l = jnp.maximum(l_scr[...], 1e-30)
        out = acc_scr[...] / l
        for tau in range(n_t):
            outd = acc_d_scr[tau] / l - (mu_d_scr[tau] / l) * out
            # contract, never store
            out_ref[0, 0, tau:tau + 1, :] = jnp.sum(gy * outd, keepdims=True)


def swa_attention_mt_jvps_kernel(q, k, v, qds, kds, vds, gy, *, window,
                                 block_q=128, block_k=128, interpret: bool,
                                 scale=None, n_heads=None, kv_groups=1):
    """Fused jvp-contraction epilogue of multi-tangent flash SWA: all T
    scalars <gy, outd_t> with NO (T, B*H, S, hd) tangent output. Same
    operand contract as ``swa_attention_mt_kernel`` plus gy: (B*H, S, hd);
    returns per-block partials (B*H, S/block_q, T, 1) fp32, summed by the
    caller (ops.py)."""
    BH, S, hd = q.shape
    T = qds.shape[0]
    assert S % block_q == 0 and S % block_k == 0
    n_heads = BH if n_heads is None else n_heads
    n_k_total, n_kv_steps, banded, scale = _plan(S, hd, window, block_q,
                                                 block_k, scale)

    grid = (BH, S // block_q, n_kv_steps)
    kv_map = functools.partial(_kv_block_index, block_q=block_q,
                               block_k=block_k, window=window,
                               n_k_total=n_k_total, banded=banded)
    kv_head = functools.partial(_kv_head_index, n_heads=n_heads,
                                kv_groups=kv_groups)
    kernel = functools.partial(_mt_jvps_kernel, block_q=block_q,
                               block_k=block_k, window=window,
                               n_kv_steps=n_kv_steps, n_k_total=n_k_total,
                               scale=scale, banded=banded, n_t=T)
    q_spec = pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, hd),
                           lambda b, i, s: (kv_head(b), kv_map(i, s), 0))
    qd_spec = pl.BlockSpec((T, 1, block_q, hd), lambda b, i, s: (0, b, i, 0))
    kvd_spec = pl.BlockSpec(
        (T, 1, block_k, hd),
        lambda b, i, s: (0, kv_head(b), kv_map(i, s), 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, qd_spec, kvd_spec, kvd_spec,
                  q_spec],
        # (T, 1) trailing dims span the whole array: a (1, T) block of a
        # (S/block_q, T) array would break the TPU's (8, 128) tiling rule
        out_specs=pl.BlockSpec((1, 1, T, 1), lambda b, i, s: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S // block_q, T, 1),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((T, block_q, 1), jnp.float32),
            pltpu.VMEM((T, block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, qds, kds, vds, gy)


def swa_attention_mt_kernel(q, k, v, qds, kds, vds, *, window, block_q=128,
                            block_k=128, interpret: bool, scale=None,
                            n_heads=None, kv_groups=1, emit_primal=True):
    """Multi-tangent flash SWA: q/k/v as in ``swa_attention_kernel``;
    qds: (T, B*H, S, hd), kds/vds: (T, B*KV, S, hd). Returns
    (out (B*H,S,hd), outds (T,B*H,S,hd)), or outds only when
    ``emit_primal=False`` (AD dispatch tangent route — the primal
    online-softmax walk still runs; the tangents need p and l)."""
    BH, S, hd = q.shape
    T = qds.shape[0]
    assert S % block_q == 0 and S % block_k == 0
    n_heads = BH if n_heads is None else n_heads
    n_k_total, n_kv_steps, banded, scale = _plan(S, hd, window, block_q,
                                                 block_k, scale)

    grid = (BH, S // block_q, n_kv_steps)
    kv_map = functools.partial(_kv_block_index, block_q=block_q,
                               block_k=block_k, window=window,
                               n_k_total=n_k_total, banded=banded)
    kv_head = functools.partial(_kv_head_index, n_heads=n_heads,
                                kv_groups=kv_groups)
    kernel = functools.partial(_mt_kernel, block_q=block_q, block_k=block_k,
                               window=window, n_kv_steps=n_kv_steps,
                               n_k_total=n_k_total, scale=scale,
                               banded=banded, n_t=T, emit_primal=emit_primal)
    q_spec = pl.BlockSpec((1, block_q, hd), lambda b, i, s: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, hd),
                           lambda b, i, s: (kv_head(b), kv_map(i, s), 0))
    qd_spec = pl.BlockSpec((T, 1, block_q, hd), lambda b, i, s: (0, b, i, 0))
    kvd_spec = pl.BlockSpec(
        (T, 1, block_k, hd),
        lambda b, i, s: (0, kv_head(b), kv_map(i, s), 0))
    out_specs = [qd_spec]
    out_shape = [jax.ShapeDtypeStruct((T, BH, S, hd), q.dtype)]
    if emit_primal:
        out_specs.insert(0, q_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct((BH, S, hd), q.dtype))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, qd_spec, kvd_spec, kvd_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((T, block_q, 1), jnp.float32),
            pltpu.VMEM((T, block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, qds, kds, vds)
    return outs if emit_primal else outs[0]
