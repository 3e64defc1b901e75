"""RWKV6 WKV recurrence — Pallas TPU kernel.

Grid: (B*H, S/block_s). The (hd, hd) state matrix lives in VMEM scratch and
persists across the sequential S dimension; each grid step streams one
(block_s, hd) tile of r/k/v/w through VMEM and walks it with a fori_loop.
Within a step the per-token update is rank-1 (outer product) + elementwise
decay — VPU work with an MXU-friendly (hd x hd) layout.

Compared to the pure-jnp lax.scan reference this removes the per-token HBM
round-trip of the state (the dominant cost on TPU for hd=64: 2*hd*hd*4 bytes
per token vs ~6*hd*hd FLOPs — arithmetic intensity < 1 without the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rows(u):
    """(..., rows, hd) -> (..., rows, 1, hd). A (1, hd) block of a
    (rows, hd) array breaks the TPU's (8, 128) block-tiling rule; a
    (1, 1, hd) block spans the full trailing dims of the reshaped array."""
    return u.reshape(u.shape[:-1] + (1, u.shape[-1]))


def _u_spec(hd):
    return pl.BlockSpec((1, 1, hd), lambda b, s: (b, 0, 0))


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_scr,
            *, block_s: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    u = u_ref[0, 0]                                 # (1, 1, hd) -> (hd,)

    def step(t, _):
        rt = r_ref[0, t, :]                         # (hd,)
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        s = state_scr[...]                          # (hd, hd)
        kv = kt[:, None] * vt[None, :]              # rank-1 outer product
        yt = ((s + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        state_scr[...] = wt[:, None] * s + kv
        y_ref[0, t, :] = yt.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, block_s, step, ())


def wkv6_scan_kernel(r, k, v, w, u, *, block_s: int = 64, interpret: bool):
    """r,k,v,w: (BH, S, hd) fp32; u: (BH, hd). Returns y (BH, S, hd)."""
    BH, S, hd = r.shape
    assert S % block_s == 0
    grid = (BH, S // block_s)
    kernel = functools.partial(_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            _u_spec(hd),
        ],
        out_specs=pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, _rows(u))


def _mt_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, rd_ref, kd_ref, vd_ref,
               wd_ref, *rest, block_s: int, n_t: int, has_ud: bool,
               emit_primal: bool):
    rest = list(rest)
    ud_ref = rest.pop(0) if has_ud else None
    y_ref = rest.pop(0) if emit_primal else None
    yd_ref = rest.pop(0)
    state_scr, state_d_scr = rest
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)
        state_d_scr[...] = jnp.zeros_like(state_d_scr)

    u = u_ref[0, 0]                                 # (hd,)

    def step(t, _):
        rt = r_ref[0, t, :]                         # (hd,)
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        s = state_scr[...]                          # (hd, hd)
        kv = kt[:, None] * vt[None, :]
        # the per-tangent math below re-reads s/kv BEFORE the state update,
        # and each tangent lane runs the exact op sequence of the T=1 slice
        # (independent scratch rows) -> stacked ydots are bitwise-equal to
        # T single-tangent passes
        if emit_primal:
            yt = ((s + u[:, None] * kv) * rt[:, None]).sum(axis=0)
            y_ref[0, t, :] = yt.astype(y_ref.dtype)
        for tau in range(n_t):                      # static unroll over T
            rdt = rd_ref[tau, 0, t, :]
            kdt = kd_ref[tau, 0, t, :]
            vdt = vd_ref[tau, 0, t, :]
            wdt = wd_ref[tau, 0, t, :]
            sd = state_d_scr[tau]                   # (hd, hd)
            kvd = kdt[:, None] * vt[None, :] + kt[:, None] * vdt[None, :]
            bonus_d = u[:, None] * kvd
            if has_ud:
                bonus_d = bonus_d + ud_ref[tau, 0, 0][:, None] * kv
            ydt = (((sd + bonus_d) * rt[:, None]).sum(axis=0)
                   + ((s + u[:, None] * kv) * rdt[:, None]).sum(axis=0))
            state_d_scr[tau] = wdt[:, None] * s + wt[:, None] * sd + kvd
            yd_ref[tau, 0, t, :] = ydt.astype(yd_ref.dtype)
        state_scr[...] = wt[:, None] * s + kv
        return ()

    jax.lax.fori_loop(0, block_s, step, ())


def _mt_jvps_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, rd_ref, kd_ref,
                    vd_ref, wd_ref, *rest, block_s: int, n_s: int, n_t: int,
                    has_ud: bool):
    """Contraction epilogue: the same primal-state / tangent-state walk as
    ``_mt_kernel``, but each per-token ydot_t is contracted against the
    incoming gy token on the spot — accumulated into a (T, hd) VMEM partial
    — instead of being written to HBM. Only a (T, 1) per-row partial leaves
    the kernel at the last sequence block."""
    rest = list(rest)
    ud_ref = rest.pop(0) if has_ud else None
    gy_ref = rest.pop(0)
    out_ref = rest.pop(0)
    state_scr, state_d_scr, acc_j = rest
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)
        state_d_scr[...] = jnp.zeros_like(state_d_scr)
        acc_j[...] = jnp.zeros_like(acc_j)

    u = u_ref[0, 0]                                 # (hd,)

    def step(t, _):
        rt = r_ref[0, t, :]                         # (hd,)
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        gt = gy_ref[0, t, :].astype(jnp.float32)
        s = state_scr[...]                          # (hd, hd)
        kv = kt[:, None] * vt[None, :]
        for tau in range(n_t):                      # static unroll over T
            rdt = rd_ref[tau, 0, t, :]
            kdt = kd_ref[tau, 0, t, :]
            vdt = vd_ref[tau, 0, t, :]
            wdt = wd_ref[tau, 0, t, :]
            sd = state_d_scr[tau]                   # (hd, hd)
            kvd = kdt[:, None] * vt[None, :] + kt[:, None] * vdt[None, :]
            bonus_d = u[:, None] * kvd
            if has_ud:
                bonus_d = bonus_d + ud_ref[tau, 0, 0][:, None] * kv
            ydt = (((sd + bonus_d) * rt[:, None]).sum(axis=0)
                   + ((s + u[:, None] * kv) * rdt[:, None]).sum(axis=0))
            state_d_scr[tau] = wdt[:, None] * s + wt[:, None] * sd + kvd
            acc_j[tau] += gt * ydt                  # contract, never store
        state_scr[...] = wt[:, None] * s + kv
        return ()

    jax.lax.fori_loop(0, block_s, step, ())

    @pl.when(si == n_s - 1)
    def _finish():
        out_ref[0] = acc_j[...].sum(axis=1, keepdims=True)


def wkv6_scan_mt_jvps_kernel(r, k, v, w, u, rds, kds, vds, wds, gy, uds=None,
                             *, block_s: int = 64, interpret: bool):
    """Fused jvp-contraction epilogue of the multi-tangent WKV recurrence:
    all T scalars <gy, ydot_t> with NO (T, BH, S, hd) tangent output — the
    per-token ydots are contracted against gy in VMEM as the state walk
    produces them. Returns per-row partials (BH, T, 1) fp32, summed by the
    caller (ops.py). Same operand contract as ``wkv6_scan_mt_kernel`` plus
    gy: (BH, S, hd)."""
    BH, S, hd = r.shape
    T = rds.shape[0]
    assert S % block_s == 0
    has_ud = uds is not None
    n_s = S // block_s
    grid = (BH, n_s)
    kernel = functools.partial(_mt_jvps_kernel, block_s=block_s, n_s=n_s,
                               n_t=T, has_ud=has_ud)
    seq_spec = pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0))
    seq_spec_t = pl.BlockSpec((T, 1, block_s, hd), lambda b, s: (0, b, s, 0))
    in_specs = [seq_spec] * 4 + [_u_spec(hd)] + [seq_spec_t] * 4
    operands = [r, k, v, w, _rows(u), rds, kds, vds, wds]
    if has_ud:
        in_specs.append(
            pl.BlockSpec((T, 1, 1, hd), lambda b, s: (0, b, 0, 0)))
        operands.append(_rows(uds))
    in_specs.append(seq_spec)                       # gy
    operands.append(gy)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, 1), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((T, hd, hd), jnp.float32),
                        pltpu.VMEM((T, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def wkv6_scan_mt_kernel(r, k, v, w, u, rds, kds, vds, wds, uds=None, *,
                        block_s: int = 64, interpret: bool,
                        emit_primal: bool = True):
    """Multi-tangent WKV recurrence: one pass over the primal r/k/v/w
    produces y plus all T ydots (same amortize-the-primal design as
    ``lora_dual_mt_kernel`` — the tangent state recurrence

        Sd_t = wd_t ∘ S_{t-1} + w_t ∘ Sd_{t-1} + kd_t v_t^T + k_t vd_t^T
        yd_t = rd_t^T (S_{t-1} + (u∘k_t) v_t^T)
             + r_t^T (Sd_{t-1} + (u∘kd_t + ud∘k_t) v_t^T + (u∘k_t) vd_t^T)

    shares the primal S walk across all T tangents).

    r,k,v,w: (BH, S, hd) fp32; u: (BH, hd); rds..wds: (T, BH, S, hd);
    uds: (T, BH, hd) or None (frozen u — the SPRY case). Returns
    (y (BH,S,hd), ydots (T,BH,S,hd)), or ydots only when
    ``emit_primal=False`` (the AD dispatch tangent route)."""
    BH, S, hd = r.shape
    T = rds.shape[0]
    assert S % block_s == 0
    has_ud = uds is not None
    grid = (BH, S // block_s)
    kernel = functools.partial(_mt_kernel, block_s=block_s, n_t=T,
                               has_ud=has_ud, emit_primal=emit_primal)
    seq_spec = pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0))
    seq_spec_t = pl.BlockSpec((T, 1, block_s, hd), lambda b, s: (0, b, s, 0))
    in_specs = [seq_spec] * 4 + [_u_spec(hd)] + [seq_spec_t] * 4
    operands = [r, k, v, w, _rows(u), rds, kds, vds, wds]
    if has_ud:
        in_specs.append(
            pl.BlockSpec((T, 1, 1, hd), lambda b, s: (0, b, 0, 0)))
        operands.append(_rows(uds))
    out_specs = [seq_spec_t]
    out_shape = [jax.ShapeDtypeStruct((T, BH, S, hd), jnp.float32)]
    if emit_primal:
        out_specs.insert(0, seq_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct((BH, S, hd), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((T, hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return outs if emit_primal else outs[0]
