"""Mamba2 state recurrence — Pallas TPU kernel.

Grid: (B*H, S/block_s). The (hd, N) state matrix lives in VMEM scratch and
persists across the sequential S dimension — the same tangent-state-scratch
design as ``kernels/wkv6_scan`` (which has a data-dependent *elementwise*
decay; Mamba2's decay is a scalar per head and token, so the per-token
update is a scalar-scaled state plus a rank-1 outer product).

B_t / C_t are shared across the H heads of a batch row, so their BlockSpec
index maps fold the flattened (b*H + h) grid row back to batch row b — the
H× repeated-B/C HBM blowup of a naive pre-broadcast never materializes
(same trick as the GQA kv maps in ``kernels/swa_attention``).

The multi-tangent (mt) variant walks T stacked tangent states alongside the
primal:

    hd_t = decayd_t * h_{t-1} + decay_t * hd_{t-1} + xdtd_t B_t^T + xdt_t Bd_t^T
    yd_t = hd_t C_t + h_t Cd_t

one pass over the primal operands serves all T tangents (the batched
K-perturbation estimator's hot loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decay_spec(block_s):
    """The per-(row, token) scalar decay rides as (rows, S, 1): a
    (1, block_s) block of a (rows, S) array breaks the TPU's (8, 128)
    block-tiling rule, while (1, block_s, 1) tiles S on sublanes."""
    return pl.BlockSpec((1, block_s, 1), lambda b, s: (b, s, 0))


def _kernel(x_ref, b_ref, c_ref, d_ref, y_ref, state_scr, *, block_s: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def step(t, _):
        xt = x_ref[0, t, :]                         # (hd,)
        bt = b_ref[0, t, :]                         # (N,)
        ct = c_ref[0, t, :]
        dct = d_ref[0, t, 0]                        # per-head scalar decay
        h = dct * state_scr[...] + xt[:, None] * bt[None, :]
        y_ref[0, t, :] = (h * ct[None, :]).sum(axis=1).astype(y_ref.dtype)
        state_scr[...] = h
        return ()

    jax.lax.fori_loop(0, block_s, step, ())


def mamba2_scan_kernel(xdt, bmat, cmat, decay, *, n_heads: int,
                       block_s: int = 64, interpret: bool):
    """xdt: (BH, S, hd) fp32; bmat,cmat: (B, S, N); decay: (BH, S).
    Returns y (BH, S, hd) fp32. ``n_heads`` folds grid row bh back to batch
    row bh // n_heads for the shared B/C streams."""
    BH, S, hd = xdt.shape
    N = bmat.shape[-1]
    assert S % block_s == 0
    grid = (BH, S // block_s)
    kernel = functools.partial(_kernel, block_s=block_s)
    bc_spec = pl.BlockSpec((1, block_s, N),
                           lambda b, s: (b // n_heads, s, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            bc_spec,
            bc_spec,
            _decay_spec(block_s),
        ],
        out_specs=pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, bmat, cmat, decay[..., None])


def _mt_kernel(x_ref, b_ref, c_ref, d_ref, xd_ref, bd_ref, cd_ref, dd_ref,
               *rest, block_s: int, n_t: int, emit_primal: bool):
    rest = list(rest)
    y_ref = rest.pop(0) if emit_primal else None
    yd_ref = rest.pop(0)
    state_scr, state_d_scr = rest
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)
        state_d_scr[...] = jnp.zeros_like(state_d_scr)

    def step(t, _):
        xt = x_ref[0, t, :]                         # (hd,)
        bt = b_ref[0, t, :]                         # (N,)
        ct = c_ref[0, t, :]
        dct = d_ref[0, t, 0]
        s = state_scr[...]                          # (hd, N)
        h = dct * s + xt[:, None] * bt[None, :]
        if emit_primal:
            y_ref[0, t, :] = (h * ct[None, :]).sum(axis=1).astype(y_ref.dtype)
        # each tangent lane re-reads the pre-update state s and runs the
        # exact op sequence of the T=1 slice on its own scratch row ->
        # stacked ydots are bitwise-equal to T single-tangent passes
        for tau in range(n_t):                      # static unroll over T
            xdt_t = xd_ref[tau, 0, t, :]
            bdt = bd_ref[tau, 0, t, :]
            cdt = cd_ref[tau, 0, t, :]
            ddt = dd_ref[tau, 0, t, 0]
            sd = state_d_scr[tau]                   # (hd, N)
            hd_t = (ddt * s + dct * sd + xdt_t[:, None] * bt[None, :]
                    + xt[:, None] * bdt[None, :])
            ydt = ((hd_t * ct[None, :]).sum(axis=1)
                   + (h * cdt[None, :]).sum(axis=1))
            state_d_scr[tau] = hd_t
            yd_ref[tau, 0, t, :] = ydt.astype(yd_ref.dtype)
        state_scr[...] = h
        return ()

    jax.lax.fori_loop(0, block_s, step, ())


def _mt_jvps_kernel(x_ref, b_ref, c_ref, d_ref, xd_ref, bd_ref, cd_ref,
                    dd_ref, gy_ref, out_ref, state_scr, state_d_scr, acc_j,
                    *, block_s: int, n_s: int, n_t: int):
    """Contraction epilogue: the same primal-state / tangent-state walk as
    ``_mt_kernel``, but each per-token ydot_t is contracted against the
    incoming gy token on the spot — accumulated into a (T, hd) VMEM partial
    — instead of being written to HBM. Only a (T, 1) per-row partial leaves
    the kernel at the last sequence block."""
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)
        state_d_scr[...] = jnp.zeros_like(state_d_scr)
        acc_j[...] = jnp.zeros_like(acc_j)

    def step(t, _):
        xt = x_ref[0, t, :]                         # (hd,)
        bt = b_ref[0, t, :]                         # (N,)
        ct = c_ref[0, t, :]
        dct = d_ref[0, t, 0]
        gt = gy_ref[0, t, :].astype(jnp.float32)
        s = state_scr[...]                          # (hd, N)
        h = dct * s + xt[:, None] * bt[None, :]
        # each tangent lane re-reads the pre-update state s and runs the
        # exact op sequence of the T=1 slice on its own scratch row ->
        # stacked partials are bitwise-equal to T single-tangent passes
        for tau in range(n_t):                      # static unroll over T
            xdt_t = xd_ref[tau, 0, t, :]
            bdt = bd_ref[tau, 0, t, :]
            cdt = cd_ref[tau, 0, t, :]
            ddt = dd_ref[tau, 0, t, 0]
            sd = state_d_scr[tau]                   # (hd, N)
            hd_t = (ddt * s + dct * sd + xdt_t[:, None] * bt[None, :]
                    + xt[:, None] * bdt[None, :])
            ydt = ((hd_t * ct[None, :]).sum(axis=1)
                   + (h * cdt[None, :]).sum(axis=1))
            state_d_scr[tau] = hd_t
            acc_j[tau] += gt * ydt                  # contract, never store
        state_scr[...] = h
        return ()

    jax.lax.fori_loop(0, block_s, step, ())

    @pl.when(si == n_s - 1)
    def _finish():
        out_ref[0] = acc_j[...].sum(axis=1, keepdims=True)


def mamba2_scan_mt_jvps_kernel(xdt, bmat, cmat, decay, xdtds, bds, cds,
                               decayds, gy, *, n_heads: int, block_s: int = 64,
                               interpret: bool):
    """Fused jvp-contraction epilogue of the multi-tangent Mamba2
    recurrence: all T scalars <gy, ydot_t> with NO (T, BH, S, hd) tangent
    output — the per-token ydots are contracted against gy in VMEM as the
    state walk produces them. Returns per-row partials (BH, T, 1) fp32,
    summed by the caller (ops.py). Same operand contract as
    ``mamba2_scan_mt_kernel`` plus gy: (BH, S, hd)."""
    BH, S, hd = xdt.shape
    N = bmat.shape[-1]
    T = xdtds.shape[0]
    assert S % block_s == 0
    n_s = S // block_s
    grid = (BH, n_s)
    kernel = functools.partial(_mt_jvps_kernel, block_s=block_s, n_s=n_s,
                               n_t=T)
    seq_spec = pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0))
    seq_spec_t = pl.BlockSpec((T, 1, block_s, hd), lambda b, s: (0, b, s, 0))
    bc_spec = pl.BlockSpec((1, block_s, N),
                           lambda b, s: (b // n_heads, s, 0))
    bcd_spec = pl.BlockSpec((T, 1, block_s, N),
                            lambda b, s: (0, b // n_heads, s, 0))
    in_specs = [
        seq_spec, bc_spec, bc_spec,
        _decay_spec(block_s),
        seq_spec_t, bcd_spec, bcd_spec,
        pl.BlockSpec((T, 1, block_s, 1), lambda b, s: (0, b, s, 0)),
        seq_spec,                                   # gy
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, 1), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32),
                        pltpu.VMEM((T, hd, N), jnp.float32),
                        pltpu.VMEM((T, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, bmat, cmat, decay[..., None], xdtds, bds, cds,
      decayds[..., None], gy)


def mamba2_scan_mt_kernel(xdt, bmat, cmat, decay, xdtds, bds, cds, decayds,
                          *, n_heads: int, block_s: int = 64, interpret: bool,
                          emit_primal: bool = True):
    """Multi-tangent Mamba2 recurrence: one pass over the primal operands
    produces y plus all T ydots.

    xdt: (BH, S, hd); bmat,cmat: (B, S, N); decay: (BH, S); tangent stacks
    lead with T (xdtds (T,BH,S,hd); bds,cds (T,B,S,N); decayds (T,BH,S)).
    Returns (y (BH,S,hd), ydots (T,BH,S,hd)), or ydots only when
    ``emit_primal=False`` (the AD dispatch tangent route)."""
    BH, S, hd = xdt.shape
    N = bmat.shape[-1]
    T = xdtds.shape[0]
    assert S % block_s == 0
    grid = (BH, S // block_s)
    kernel = functools.partial(_mt_kernel, block_s=block_s, n_t=T,
                               emit_primal=emit_primal)
    seq_spec = pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0))
    seq_spec_t = pl.BlockSpec((T, 1, block_s, hd), lambda b, s: (0, b, s, 0))
    bc_spec = pl.BlockSpec((1, block_s, N),
                           lambda b, s: (b // n_heads, s, 0))
    bcd_spec = pl.BlockSpec((T, 1, block_s, N),
                            lambda b, s: (0, b // n_heads, s, 0))
    in_specs = [
        seq_spec, bc_spec, bc_spec,
        _decay_spec(block_s),
        seq_spec_t, bcd_spec, bcd_spec,
        pl.BlockSpec((T, 1, block_s, 1), lambda b, s: (0, b, s, 0)),
    ]
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
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32),
                        pltpu.VMEM((T, hd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, bmat, cmat, decay[..., None], xdtds, bds, cds,
      decayds[..., None])
    return outs if emit_primal else outs[0]
