"""RWKV6 WKV recurrence — chunk-parallel Pallas TPU kernels.

    y_t = r_t^T (S_{t-1} + (u∘k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

Operands keep the model's layout, (B, S, H*hd): a block holds ``block_s``
tokens of G adjacent heads side by side on the lanes (G*hd = 128 for
rwkv6's heads of 64), so every vector op runs on full lanes and no
transpose of the operands is needed. Grid: (B, H/G, S/block_s); each grid
step walks its block in sub-chunks of ``CHUNK`` tokens. Within a sub-chunk
the recurrence splits, per head, into

    y = A v + (r ∘ P) S_0        S_C = diag(p_C) S_0 + (k ∘ D_C)^T v

with the pairwise decays D_t[j] = prod_{j<m<t} w_m (key j, query t),
A[t, j] = sum_i r_ti k_ji D_t[j, i] (+ the u bonus on the diagonal),
P[t] = prod_{m<t} w_m and p_C = P[C]. The products with v and the state
run on the MXU at full f32 precision, for the G heads at once: the states
of the G heads are the diagonal blocks of one (G*hd, G*hd) matrix, carried
in VMEM across sub-chunks. The decay products are built by multiplying by
one w_t per token, as the sequential recurrence does, never from
differences of cumulative log-decays: w = 0, w near 1 and any w-tangent give
the sequential values without a log guard.

Tangents (the ``_mt`` kernels) follow the product rule through the same
primal factors: Dd_t and Pd_t by the recursion Dd_{t+1} = Dd_t w_t + D_t wd_t,
dA from (rd, kd, Dd), and

    yd   = dA v + A vd + (rd∘P + r∘Pd) S_0 + (r∘P) Sd_0
    Sd_C = diag(pd_C) S_0 + diag(p_C) Sd_0 + (kd∘D_C + k∘Dd_C)^T v
           + (k∘D_C)^T vd

The primal pieces (D_t, A, P, p_C) are computed once per sub-chunk and kept
in VMEM for all T tangents; each tangent runs the same op sequence on its own
scratch, so T stacked tangents are bitwise-equal to T single-tangent passes.
States are stored transposed, (value, key), so that every decay is a lane
broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 32                      # tokens per sub-chunk (the pairwise block)
_F32 = jnp.float32


def _block_lanes(width, hd):
    """Lanes of a block, G*hd: as many adjacent heads of a (..., width)
    row as fill at most 128 lanes, in a block whose lane width is a
    multiple of 128 or the whole row."""
    H = width // hd
    for G in range(H, 0, -1):
        if H % G == 0 and G * hd <= 128 and (G * hd % 128 == 0 or G == H):
            return G * hd
    raise ValueError(f"no lane-aligned block of whole heads: {H} x {hd}")


def _dot(a, b, ca, cb):
    """Contract dim ``ca`` of a with dim ``cb`` of b, f32 at full precision."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


class _Heads:
    """Lane bookkeeping of G heads of ``hd`` side by side."""

    def __init__(self, lw, hd):
        C = CHUNK
        self.G = lw // hd
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lw), 1) // hd
        self.masks = [lane == g for g in range(self.G)]
        self.rows = jax.lax.broadcasted_iota(jnp.int32, (C, lw), 0)
        self.lanes = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.diag = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
                     == self.lanes)
        head = jax.lax.broadcasted_iota(jnp.int32, (lw, lw), 0) // hd
        self.block = head == head.T                 # the states' diagonal

    def sums(self, x):
        """(C, lw) -> per head, the (C, 1) sums over its lanes."""
        return [jnp.sum(jnp.where(m, x, 0.0), axis=1, keepdims=True)
                for m in self.masks]

    def split(self, x):
        """(n, lw) -> per head, x with the other heads' lanes zeroed."""
        return [jnp.where(m, x, 0.0) for m in self.masks]

    def put(self, cols, t, into):
        """Per head, the (C, 1) column into lane t of the (C, C) scores."""
        return [jnp.where(self.lanes == t, c, a) for c, a in zip(cols, into)]

    def put_diag(self, cols, into):
        """Per head, the (C, 1) column onto the diagonal of the scores."""
        return [jnp.where(self.diag, c, a) for c, a in zip(cols, into)]

    def mix(self, scores, vals):
        """sum over heads of scores_g^T @ vals_g: per head, the (C, C)
        transposed scores against that head's lanes of the (C, lw) values,
        in one matmul."""
        return _dot(jnp.concatenate(scores), jnp.concatenate(
            [p for v in vals for p in self.split(v)]), 0, 0)


def _chunk_primal(hs, r, k, w, u, dec_scr=None, kdec_scr=None):
    """Pairwise decays of one sub-chunk. r, k, w: (C, lw); u: (1, lw).

    Returns per head the scores transposed, at[j, t] = A[t, j] (bonus on the
    diagonal), the prefix products pm[t] = P[t], D_C (C, lw) and p_C (1, lw).
    Leaves D_t and k∘D_t in ``dec_scr[t]`` / ``kdec_scr[t]`` for the tangent
    pass when given."""
    C, lw = r.shape
    d = jnp.zeros((C, lw), _F32)
    p = jnp.ones((1, lw), _F32)
    at = [jnp.zeros((C, C), _F32)] * hs.G
    pm = jnp.zeros((C, lw), _F32)
    for t in range(C):
        kdec_t = k * d
        if dec_scr is not None:
            dec_scr[t] = d
            kdec_scr[t] = kdec_t
        at = hs.put(hs.sums(kdec_t * r[t:t + 1]), t, at)
        pm = jnp.where(hs.rows == t, p, pm)
        w_t = w[t:t + 1]
        d = jnp.where(hs.rows == t, 1.0, d * w_t)
        p = p * w_t
    at = hs.put_diag(hs.sums(r * u * k), at)
    return at, pm, d, p


def _chunk_tangent(hs, r, k, w, u, rd, kd, wd, ud, pm, dec_scr, kdec_scr):
    """Product rule through ``_chunk_primal`` for one tangent (``ud`` None
    for a frozen u). Returns dA transposed per head, rd∘P + r∘Pd, k∘Dd_C
    and pd_C."""
    C, lw = r.shape
    e = jnp.zeros((C, lw), _F32)                    # k ∘ Dd_t
    pd = jnp.zeros((1, lw), _F32)
    dat = [jnp.zeros((C, C), _F32)] * hs.G
    pdm = jnp.zeros((C, lw), _F32)
    for t in range(C):
        d_t = dec_scr[t]
        kdec_t = kdec_scr[t]
        dat = hs.put(hs.sums(kdec_t * rd[t:t + 1]
                             + (kd * d_t + e) * r[t:t + 1]), t, dat)
        pdm = jnp.where(hs.rows == t, pd, pdm)
        w_t, wd_t = w[t:t + 1], wd[t:t + 1]
        e = e * w_t + kdec_t * wd_t
        pd = pd * w_t + pm[t:t + 1] * wd_t
    bonus = rd * u * k + r * u * kd
    if ud is not None:
        bonus = bonus + r * ud * k
    dat = hs.put_diag(hs.sums(bonus), dat)
    return dat, rd * pm + r * pdm, e, pd


def _seq(c):
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _scratch(lw, T):
    C = CHUNK
    return [pltpu.VMEM((lw, lw), _F32),             # states (value, key)
            pltpu.VMEM((T, lw, lw), _F32),          # tangent states
            pltpu.VMEM((C, C, lw), _F32),           # D_t
            pltpu.VMEM((C, C, lw), _F32)]           # k ∘ D_t


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_scr, *,
            block_s: int, hd: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    u = u_ref[...]                                  # (1, lw)
    hs = _Heads(u.shape[1], hd)

    def body(c, _):
        rows = _seq(c)
        r, k, v, w = (ref[0, rows, :] for ref in (r_ref, k_ref, v_ref, w_ref))
        st = st_scr[...]
        at, pm, d_end, p_end = _chunk_primal(hs, r, k, w, u)
        y_ref[0, rows, :] = hs.mix(at, [v]) + _dot(r * pm, st, 1, 1)
        st_scr[...] = st * p_end + jnp.where(
            hs.block, _dot(v, k * d_end, 0, 0), 0.0)
        return ()

    jax.lax.fori_loop(0, block_s // CHUNK, body, ())


def _seq_spec(block_s, lw):
    return pl.BlockSpec((1, block_s, lw), lambda b, g, s: (b, s, g))


def _grid(r, block_s, lw):
    B, S, HW = r.shape
    return (B, HW // lw, S // block_s)


def wkv6_scan_kernel(r, k, v, w, u, *, hd: int, block_s: int = 64,
                     interpret: bool):
    """r,k,v,w: (B, S, H*hd) fp32; u: (1, H*hd). Returns y (B, S, H*hd).
    ``block_s`` divides S and is a multiple of ``CHUNK``."""
    B, S, HW = r.shape
    assert S % block_s == 0 and block_s % CHUNK == 0
    lw = _block_lanes(HW, hd)
    seq_spec = _seq_spec(block_s, lw)
    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, hd=hd),
        grid=_grid(r, block_s, lw),
        in_specs=[seq_spec] * 4 + [
            pl.BlockSpec((1, lw), lambda b, g, s: (0, g))],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct(r.shape, _F32),
        scratch_shapes=[pltpu.VMEM((lw, lw), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)


def _mt_walk(ins, scr, *, block_s, n_t, has_ud, hd, primal_out,
             tangent_out):
    """The walk both multi-tangent kernels share: per sub-chunk, the primal
    pieces, then each tangent through them and its state update.
    ``primal_out(rows, pieces)`` returns what ``tangent_out(tau, rows,
    pieces, ctx, dat, xd, vd, sdt)`` needs besides; both see the incoming
    states."""
    r_ref, k_ref, v_ref, w_ref, u_ref, rd_ref, kd_ref, vd_ref, wd_ref, \
        ud_ref = ins
    st_scr, sdt_scr, dec_scr, kdec_scr = scr

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)
        sdt_scr[...] = jnp.zeros_like(sdt_scr)

    u = u_ref[...]                                  # (1, lw)
    hs = _Heads(u.shape[1], hd)

    def body(c, _):
        rows = _seq(c)
        r, k, v, w = (ref[0, rows, :] for ref in (r_ref, k_ref, v_ref, w_ref))
        st = st_scr[...]
        at, pm, d_end, p_end = _chunk_primal(hs, r, k, w, u, dec_scr,
                                             kdec_scr)
        kend = k * d_end
        pieces = dict(hs=hs, v=v, st=st, at=at, x=r * pm)
        ctx = primal_out(rows, pieces)

        def one(tau, _):
            rd, kd, vd, wd = (ref[tau, 0, rows, :]
                              for ref in (rd_ref, kd_ref, vd_ref, wd_ref))
            ud = ud_ref[tau] if has_ud else None
            sdt = sdt_scr[tau]
            dat, xd, e_end, pd_end = _chunk_tangent(
                hs, r, k, w, u, rd, kd, wd, ud, pm, dec_scr, kdec_scr)
            tangent_out(tau, rows, pieces, ctx, dat, xd, vd, sdt)
            sdt_scr[tau] = (sdt * p_end + st * pd_end + jnp.where(
                hs.block, _dot(jnp.concatenate([v, vd]),
                               jnp.concatenate([kd * d_end + e_end, kend]),
                               0, 0), 0.0))
            return ()

        jax.lax.fori_loop(0, n_t, one, ())
        st_scr[...] = st * p_end + jnp.where(
            hs.block, _dot(v, kend, 0, 0), 0.0)
        return ()

    jax.lax.fori_loop(0, block_s // CHUNK, body, ())


def _split(refs, n_in, n_out, has_ud):
    """Kernel refs -> (the ten walk inputs, ud None when frozen), extra
    inputs, outputs, scratch."""
    ud = refs[9] if has_ud else None
    rest = refs[9 + has_ud:]
    return (list(refs[:9]) + [ud], rest[:n_in], rest[n_in:n_in + n_out],
            rest[n_in + n_out:])


def _mt_kernel(*refs, block_s: int, n_t: int, has_ud: bool, hd: int,
               emit_primal: bool):
    ins, _, outs, scr = _split(refs, 0, 1 + emit_primal, has_ud)

    def primal_out(rows, p):
        if emit_primal:
            outs[0][0, rows, :] = (p["hs"].mix(p["at"], [p["v"]])
                                   + _dot(p["x"], p["st"], 1, 1))

    def tangent_out(tau, rows, p, _, dat, xd, vd, sdt):
        outs[-1][tau, 0, rows, :] = (
            p["hs"].mix(dat + p["at"], [p["v"], vd])
            + _dot(jnp.concatenate([xd, p["x"]], 1),
                   jnp.concatenate([p["st"], sdt], 1), 1, 1))

    _mt_walk(ins, scr, block_s=block_s, n_t=n_t, has_ud=has_ud, hd=hd,
             primal_out=primal_out, tangent_out=tangent_out)


def _total(x):
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _mt_jvps_kernel(*refs, block_s: int, n_s: int, n_t: int, has_ud: bool,
                    hd: int):
    """Contraction epilogue: the ``_mt_kernel`` walk, but no ydot is
    formed. gy is carried through each product of the ydot once per
    sub-chunk, with the primal operands (per head),

        <gy, yd> = <v gy^T, dA^T> + <A^T gy, vd> + <gy S_0^T, rd∘P + r∘Pd>
                 + <(r∘P)^T gy, Sd_0>,

    so each tangent adds elementwise products into a (T, 1) VMEM partial.
    Only that partial per block of heads leaves the kernel at the last
    sequence block."""
    ins, (gy_ref,), (out_ref,), scr = _split(refs, 1, 1, has_ud)
    *scr, acc = scr

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def primal_out(rows, p):
        hs, gy = p["hs"], gy_ref[0, rows, :]
        return dict(
            ga=_dot(jnp.concatenate(hs.split(p["v"])), gy, 1, 1),
            gv=_dot(jnp.concatenate(p["at"], axis=1),
                    jnp.concatenate(hs.split(gy)), 1, 0),
            gx=_dot(gy, p["st"], 1, 0), gs=_dot(gy, p["x"], 0, 0))

    def tangent_out(tau, rows, p, g, dat, xd, vd, sdt):
        acc[pl.ds(tau, 1), :] += (
            _total(jnp.concatenate(dat) * g["ga"])
            + _total(vd * g["gv"] + xd * g["gx"]) + _total(sdt * g["gs"]))

    _mt_walk(ins, scr, block_s=block_s, n_t=n_t, has_ud=has_ud, hd=hd,
             primal_out=primal_out, tangent_out=tangent_out)

    @pl.when(pl.program_id(2) == n_s - 1)
    def _finish():
        out_ref[0, 0] = acc[...]


def _mt_specs(T, block_s, lw, has_ud):
    seq_spec = _seq_spec(block_s, lw)
    seq_spec_t = pl.BlockSpec((T, 1, block_s, lw),
                              lambda b, g, s: (0, b, s, g))
    in_specs = [seq_spec] * 4 + [
        pl.BlockSpec((1, lw), lambda b, g, s: (0, g))] + [seq_spec_t] * 4
    if has_ud:
        in_specs.append(pl.BlockSpec((T, 1, lw), lambda b, g, s: (0, 0, g)))
    return in_specs, seq_spec, seq_spec_t


def wkv6_scan_mt_jvps_kernel(r, k, v, w, u, rds, kds, vds, wds, gy, uds=None,
                             *, hd: int, block_s: int = 64, interpret: bool):
    """Fused jvp-contraction epilogue of the multi-tangent WKV recurrence:
    all T scalars <gy, ydot_t> with NO (T, B, S, H*hd) tangent output — each
    sub-chunk's ydots are contracted against gy in VMEM as the walk
    produces them. Returns partials (B, H/G, T, 1) fp32, summed by the
    caller (ops.py). Same operand contract as ``wkv6_scan_mt_kernel`` plus
    gy: (B, S, H*hd)."""
    B, S, HW = r.shape
    T = rds.shape[0]
    assert S % block_s == 0 and block_s % CHUNK == 0
    has_ud = uds is not None
    lw = _block_lanes(HW, hd)
    n_s = S // block_s
    in_specs, seq_spec, _ = _mt_specs(T, block_s, lw, has_ud)
    operands = [r, k, v, w, u, rds, kds, vds, wds]
    if has_ud:
        operands.append(uds)
    kernel = functools.partial(_mt_jvps_kernel, block_s=block_s, n_s=n_s,
                               n_t=T, has_ud=has_ud, hd=hd)
    return pl.pallas_call(
        kernel,
        grid=_grid(r, block_s, lw),
        in_specs=in_specs + [seq_spec],             # + gy
        out_specs=pl.BlockSpec((1, 1, T, 1), lambda b, g, s: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HW // lw, T, 1), _F32),
        scratch_shapes=_scratch(lw, T) + [pltpu.VMEM((T, 1), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, gy)


def wkv6_scan_mt_kernel(r, k, v, w, u, rds, kds, vds, wds, uds=None, *,
                        hd: int, block_s: int = 64, interpret: bool,
                        emit_primal: bool = True):
    """Multi-tangent WKV recurrence: one pass over the primal r/k/v/w
    produces y plus all T ydots (same amortize-the-primal design as
    ``lora_dual_mt_kernel``: each sub-chunk's primal pieces serve all T
    tangents).

    r,k,v,w: (B, S, H*hd) fp32; u: (1, H*hd); rds..wds: (T, B, S, H*hd);
    uds: (T, 1, H*hd) or None (frozen u — the SPRY case). Returns
    (y (B,S,H*hd), ydots (T,B,S,H*hd)), or ydots only when
    ``emit_primal=False`` (the AD dispatch tangent route)."""
    B, S, HW = r.shape
    T = rds.shape[0]
    assert S % block_s == 0 and block_s % CHUNK == 0
    has_ud = uds is not None
    lw = _block_lanes(HW, hd)
    in_specs, seq_spec, seq_spec_t = _mt_specs(T, block_s, lw, has_ud)
    operands = [r, k, v, w, u, rds, kds, vds, wds]
    if has_ud:
        operands.append(uds)
    out_specs = [seq_spec_t]
    out_shape = [jax.ShapeDtypeStruct((T,) + r.shape, _F32)]
    if emit_primal:
        out_specs.insert(0, seq_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct(r.shape, _F32))
    kernel = functools.partial(_mt_kernel, block_s=block_s, n_t=T,
                               has_ud=has_ud, hd=hd, emit_primal=emit_primal)
    outs = pl.pallas_call(
        kernel,
        grid=_grid(r, block_s, lw),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_scratch(lw, T),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return outs if emit_primal else outs[0]
