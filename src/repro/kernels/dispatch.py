"""Backend dispatch for the fused forward-gradient kernels.

``models/common.py::proj`` routes every LoRA-adapted projection through
``lora_proj`` below, whose custom-JVP rule evaluates the primal AND tangent
with the fused dual kernel instead of the pure-jnp mirror; the sequence
mixers route the same way — ``models/ssm.py`` (RWKV6) through ``wkv6_mix``,
``models/ssm.py`` (Mamba2) through ``mamba2_mix`` and
``models/attention.py`` (SWA prefill) through ``swa_attend``:

    backend 'pallas'     compiled Pallas TPU kernels (kernels/lora_dual,
                         kernels/wkv6_scan, kernels/swa_attention)
    backend 'interpret'  same kernels under the Pallas interpreter (CPU
                         validation of the exact kernel dataflow)
    backend 'jnp'        reference einsum/scan mirrors — the fast CPU path
                         (XLA fuses them; interpret-mode Pallas would be
                         orders of magnitude slower in the test suite)

Resolution: ``REPRO_LORA_BACKEND`` env var if set (one of auto | jnp |
interpret | pallas), else 'pallas' when jax's default backend is TPU, else
'jnp'. ``set_backend`` overrides per-process (tests).

The kernel route additionally requires being inside ``forward_ad_region()``
(established by core/forward_grad.py while tracing the estimator): Pallas
calls have no transpose rule, so outside that region — in particular under
``jax.grad`` in the backprop baselines — the rules always trace the jnp
mirror, keeping reverse-mode AD working on every backend. The mixer call
sites additionally gate on ``use_kernel_mixers()`` so the pure-jnp model
paths are untouched byte-for-byte on the 'jnp' backend.

Tangent-axis contract
---------------------
Under the batched K-tangent estimator (core/forward_grad.py) the tangent
side of each JVP rule is batched by vmap — tangent operands gain the
leading K axis while primal operands stay unbatched, which is exactly the
multi-tangent kernel contract (``lora_dual_mt_tangents``,
``wkv6_scan_mt_tangents``, ``swa_attention_mt_tangents``: tangents carry a
leading T axis; one pass over the primal serves all T tangents). The
tangent calls are wrapped in ``jax.custom_batching.custom_vmap`` so that
vmap-of-tangents lowers DIRECTLY to the T=K multi-tangent kernel — one
fused pallas_call per projection/mixer — instead of the Pallas default
batching rule (which would re-grid the T=1 kernel over K and recompute the
primal per tangent). A batched primal (the round's vmap over clients) vmaps
the T=1 kernel instead: Pallas's own batching rule then adds a parallel grid
axis over the lanes.

Cotangent-known route (contraction epilogues)
---------------------------------------------
When the estimator can supply the output cotangent ``gy`` of a site — the
last-mixer / loss-head pattern, where everything downstream of the site is
cheap enough to reverse once — the jvp contribution of the site collapses
to the T scalars <gy, ydot_t>, and the ``*_jvp_contract`` ops below compute
them WITHOUT ever materializing a (T, ..., N) tangent output: their
custom-vmap lowering picks the ``*_mt_jvps`` contraction-epilogue kernel
(per-tangent partials accumulated blockwise in VMEM; only per-block scalars
reach HBM) instead of ``*_mt_tangents``. On the 'jnp' backend the lora
route is the reassociated einsum mirror of the same math (still no
(T, M, N) buffer); the wkv6/swa jnp mirrors materialize-and-contract and
rely on XLA fusion — the memory claim is a kernel-backend property.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import os

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.custom_derivatives import SymbolicZero

from repro.kernels.lora_dual.ops import (
    lora_dual_mt_jvps,
    lora_dual_mt_tangents,
    lora_dual_multi,
)
from repro.kernels.mamba2_scan import ops as mamba2_ops
from repro.kernels.mamba2_scan.ref import mamba2_scan_ref
from repro.kernels.swa_attention.ops import (
    swa_attention,
    swa_attention_mt_jvps,
    swa_attention_mt_tangents,
)
from repro.kernels.swa_attention.ref import swa_attention_gqa_ref
from repro.kernels.wkv6_scan.ops import (
    wkv6_scan,
    wkv6_scan_mt_jvps,
    wkv6_scan_mt_tangents,
)
from repro.kernels.wkv6_scan.ref import wkv6_scan_ref

# Pallas calls have no transpose rule, so the kernel tangent route would
# break reverse-mode AD (the backprop baselines) if taken unconditionally.
# The kernel route is therefore gated on a trace-time region that only the
# forward-gradient estimator (core/forward_grad.py) establishes; any other
# differentiation — jax.grad/value_and_grad in the baselines, or user code —
# traces the transposable jnp mirror regardless of backend.
_fwd_region = contextvars.ContextVar("repro_forward_ad_region", default=False)


@contextlib.contextmanager
def forward_ad_region():
    """Trace-time marker: within this context, LoRA projection and sequence
    mixer tangents may lower to the (non-transposable) fused Pallas
    kernels."""
    token = _fwd_region.set(True)
    try:
        yield
    finally:
        _fwd_region.reset(token)


def in_forward_ad_region() -> bool:
    return _fwd_region.get()

_BACKENDS = ("auto", "jnp", "interpret", "pallas")
_backend_override: str | None = None


def set_backend(name: str | None) -> None:
    """Force a dispatch backend for this process (None restores 'auto')."""
    global _backend_override
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {name!r}")
    _backend_override = name


def get_backend() -> str:
    """Resolved backend: override > $REPRO_LORA_BACKEND > platform default."""
    name = _backend_override or os.environ.get("REPRO_LORA_BACKEND", "auto")
    if name not in _BACKENDS:
        raise ValueError(
            f"REPRO_LORA_BACKEND must be one of {_BACKENDS}, got {name!r}")
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return name


def use_kernel_mixers() -> bool:
    """True when the sequence-mixer call sites (models/ssm.py,
    models/attention.py) should route through the dispatched ops below:
    inside the estimator's forward-AD region on a kernel backend. On the
    'jnp' backend the model keeps its native scan/chunked paths untouched."""
    return in_forward_ad_region() and get_backend() in ("pallas", "interpret")


def _materialize(t, like):
    if isinstance(t, SymbolicZero):
        return jnp.zeros(like.shape, like.dtype)
    return t


def _vmap_fallback(axis_size, in_batched, args, f):
    """custom_vmap rule for batched primals — the round's vmap over clients,
    where every lane has its own activations: each lane is an independent
    problem, so the T=1 kernel is vmapped and Pallas's batching rule adds a
    parallel grid axis (one kernel call for all lanes)."""
    in_axes = tuple(0 if b else None for b in in_batched)
    return jax.vmap(f, in_axes=in_axes, axis_size=axis_size)(*args), True


def _stack_tangents(axis_size, tangents, batched):
    """Give every tangent the leading T axis. Unbatched tangents (e.g. a
    symbolic zero materialized at linearize time — the same constant for all
    K lanes) are broadcast, so the mt route still fires whenever the
    PRIMALS are unbatched."""
    return tuple(
        t if b else jnp.broadcast_to(t, (axis_size,) + jnp.shape(t))
        for t, b in zip(tangents, batched))


# ---------------------------------------------------------------------------
# Multi-tangent batching rules (vmap-of-tangents -> one mt kernel call)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _lora_tangent_fn(scale: float, has_xd: bool, interpret: bool):
    """Tangent-only LoRA jvp, custom-vmapped so K stacked tangents lower to
    ONE ``lora_dual_mt_tangents`` call (T=K) instead of K re-gridded T=1
    Pallas calls."""
    if has_xd:
        def base(x, w, a, b, xd, ad, bd):
            return lora_dual_mt_tangents(
                x, xd[None], w, a, ad[None], b, bd[None], scale=scale,
                interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, w, a, b, xd, ad, bd):
            xb, wb, ab, bb = in_batched[:4]
            if not (xb or wb or ab or bb):
                xd, ad, bd = _stack_tangents(axis_size, (xd, ad, bd),
                                             in_batched[4:])
                return lora_dual_mt_tangents(
                    x, xd, w, a, ad, b, bd, scale=scale,
                    interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (x, w, a, b, xd, ad, bd), base)
    else:
        def base(x, w, a, b, ad, bd):
            return lora_dual_mt_tangents(
                x, None, w, a, ad[None], b, bd[None], scale=scale,
                interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, w, a, b, ad, bd):
            xb, wb, ab, bb = in_batched[:4]
            if not (xb or wb or ab or bb):
                ad, bd = _stack_tangents(axis_size, (ad, bd), in_batched[4:])
                return lora_dual_mt_tangents(
                    x, None, w, a, ad, b, bd, scale=scale,
                    interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (x, w, a, b, ad, bd), base)
    return f


@functools.lru_cache(maxsize=None)
def _wkv6_tangent_fn(has_ud: bool, interpret: bool):
    """Tangent-only WKV6 jvp, custom-vmapped onto ``wkv6_scan_mt_tangents``
    (one pass of the primal pieces for all K tangents)."""
    if has_ud:
        def base(r, k, v, w, u, rd, kd, vd, wd, ud):
            return wkv6_scan_mt_tangents(
                r, k, v, w, u, rd[None], kd[None], vd[None], wd[None],
                ud[None], interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, r, k, v, w, u, rd, kd, vd, wd, ud):
            pb, tb = in_batched[:5], in_batched[5:]
            if not any(pb):
                rd, kd, vd, wd, ud = _stack_tangents(
                    axis_size, (rd, kd, vd, wd, ud), tb)
                return wkv6_scan_mt_tangents(
                    r, k, v, w, u, rd, kd, vd, wd, ud,
                    interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (r, k, v, w, u, rd, kd, vd, wd, ud), base)
    else:
        def base(r, k, v, w, u, rd, kd, vd, wd):
            return wkv6_scan_mt_tangents(
                r, k, v, w, u, rd[None], kd[None], vd[None], wd[None],
                interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, r, k, v, w, u, rd, kd, vd, wd):
            pb, tb = in_batched[:5], in_batched[5:]
            if not any(pb):
                rd, kd, vd, wd = _stack_tangents(axis_size,
                                                 (rd, kd, vd, wd), tb)
                return wkv6_scan_mt_tangents(
                    r, k, v, w, u, rd, kd, vd, wd, interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (r, k, v, w, u, rd, kd, vd, wd), base)
    return f


@functools.lru_cache(maxsize=None)
def _swa_tangent_fn(window, interpret: bool):
    """Tangent-only SWA jvp, custom-vmapped onto
    ``swa_attention_mt_tangents`` (one online-softmax walk for all K
    tangents)."""
    def base(q, k, v, qd, kd, vd):
        return swa_attention_mt_tangents(
            q, k, v, qd[None], kd[None], vd[None], window=window,
            interpret=interpret)[0]

    f = custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, q, k, v, qd, kd, vd):
        pb, tb = in_batched[:3], in_batched[3:]
        if not any(pb):
            qd, kd, vd = _stack_tangents(axis_size, (qd, kd, vd), tb)
            return swa_attention_mt_tangents(
                q, k, v, qd, kd, vd, window=window, interpret=interpret), True
        return _vmap_fallback(axis_size, in_batched, (q, k, v, qd, kd, vd),
                              base)
    return f


@functools.lru_cache(maxsize=None)
def _mamba2_tangent_fn(interpret: bool):
    """Tangent-only Mamba2 jvp, custom-vmapped onto
    ``mamba2_scan_mt_tangents`` (one primal state walk for all K
    tangents)."""
    def base(xdt, bm, cm, dec, xd, bd, cd, dd):
        return mamba2_ops.mamba2_scan_mt_tangents(
            xdt, bm, cm, dec, xd[None], bd[None], cd[None], dd[None],
            interpret=interpret)[0]

    f = custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, xdt, bm, cm, dec, xd, bd, cd, dd):
        pb, tb = in_batched[:4], in_batched[4:]
        if not any(pb):
            xd, bd, cd, dd = _stack_tangents(axis_size, (xd, bd, cd, dd), tb)
            return mamba2_ops.mamba2_scan_mt_tangents(
                xdt, bm, cm, dec, xd, bd, cd, dd, interpret=interpret), True
        return _vmap_fallback(axis_size, in_batched,
                              (xdt, bm, cm, dec, xd, bd, cd, dd), base)
    return f


# ---------------------------------------------------------------------------
# LoRA projection
# ---------------------------------------------------------------------------

def _lora_terms(x, a, b, scale):
    """The rank-r update s*(x@A)@B computed in A's dtype (fp32 master LoRA
    weights), mirroring the pre-dispatch pure-jnp proj numerics exactly."""
    return (x.astype(a.dtype) @ a) @ b * scale


@functools.partial(jax.custom_jvp, nondiff_argnums=(4,))
def lora_proj(x, w, a, b, scale):
    """y = x@W + s*(x@A)@B with a dispatchable fused-dual JVP rule."""
    y = x @ w
    return y + _lora_terms(x, a, b, scale).astype(y.dtype)


@functools.partial(lora_proj.defjvp, symbolic_zeros=True)
def _lora_proj_jvp(scale, primals, tangents):
    x, w, a, b = primals
    xd, wd, ad, bd = tangents
    has_xd = not isinstance(xd, SymbolicZero)
    has_wd = not isinstance(wd, SymbolicZero)
    backend = get_backend()

    if backend in ("pallas", "interpret") and in_forward_ad_region():
        # primal from the jnp mirror (must stay tangent-independent so
        # linearize can split the rule); tangents from the fused kernel —
        # one pass over x/W per tangent group. The custom-vmapped tangent fn
        # makes the batched estimator's vmap collapse K tangents into ONE
        # mt kernel call.
        y = x @ w
        y = y + _lora_terms(x, a, b, scale).astype(y.dtype)
        fn = _lora_tangent_fn(scale, has_xd, backend == "interpret")
        ad_m, bd_m = _materialize(ad, a), _materialize(bd, b)
        if has_xd:
            yd = fn(x, w, a, b, xd, ad_m, bd_m)
        else:
            yd = fn(x, w, a, b, ad_m, bd_m)
        if has_wd:  # frozen W in SPRY; handled for AD completeness
            yd = yd + (x @ wd).astype(yd.dtype)
        return y, yd

    # 'jnp': reference mirror with symbolic-zero pruning — ops whose inputs
    # carry no tangent never enter the graph (so under the batched estimator
    # only tangent-carrying terms gain the K axis)
    y = x @ w
    y = y + _lora_terms(x, a, b, scale).astype(y.dtype)

    x32 = x.astype(a.dtype)
    u = x32 @ a
    ud = None
    if has_xd:
        ud = xd.astype(a.dtype) @ a
    if not isinstance(ad, SymbolicZero):
        ud = x32 @ ad if ud is None else ud + x32 @ ad
    lo_d = None
    if ud is not None:
        lo_d = (ud @ b) * scale
    if not isinstance(bd, SymbolicZero):
        t = (u @ bd) * scale
        lo_d = t if lo_d is None else lo_d + t
    yd = jnp.zeros(y.shape, y.dtype) if (lo_d is None and not has_xd
                                         and not has_wd) else None
    if yd is None:
        yd = lo_d.astype(y.dtype) if lo_d is not None else 0.0
        if has_xd:
            yd = xd @ w + yd
        if has_wd:
            yd = yd + x @ wd
    return y, yd


# ---------------------------------------------------------------------------
# Multi-adapter LoRA projection (serving path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _lora_multi_fn(scale: float, backend: str):
    """Per-row multi-adapter projection, custom-vmapped so a batch of rows
    each carrying its own adapter index lowers to ONE ``lora_dual_multi``
    pallas_call (one pass over the shared frozen W for the whole
    heterogeneous batch) on kernel backends, and to the gathered-einsum jnp
    mirror on 'jnp'. The unbatched base is exactly the single-adapter
    ``lora_proj`` primal with the pair gathered from the page stacks, so
    every row is bitwise-equal to single-adapter serving."""
    def base(x, aidx, w, a_stack, b_stack):
        a = a_stack[aidx]
        b = b_stack[aidx]
        y = x @ w
        return y + _lora_terms(x, a, b, scale).astype(y.dtype)

    f = custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, x, aidx, w, a_stack, b_stack):
        xb, ib, wb, ab, bb = in_batched
        if xb and ib and not (wb or ab or bb):
            if backend in ("pallas", "interpret"):
                return lora_dual_multi(
                    x, aidx, w, a_stack, b_stack, scale=scale,
                    interpret=backend == "interpret"), True
            # jnp mirror: gather the per-row pairs, keep the per-row math
            # of ``base`` (x32 @ A) @ B * s — batched matmuls are
            # row-independent, so this stays bitwise per row
            a_sel = jnp.take(a_stack, aidx, axis=0)        # (B, K, r)
            b_sel = jnp.take(b_stack, aidx, axis=0)        # (B, r, N)
            y = x @ w
            u = jnp.einsum("b...k,bkr->b...r", x.astype(a_stack.dtype),
                           a_sel)
            lo = jnp.einsum("b...r,brn->b...n", u, b_sel) * scale
            return y + lo.astype(y.dtype), True
        return _vmap_fallback(axis_size, in_batched,
                              (x, aidx, w, a_stack, b_stack), base)
    return f


def lora_proj_multi(x, idx, w, a_stack, b_stack, scale=1.0):
    """Batched multi-adapter projection: row b of ``x`` (B, ..., K)
    projects through adapter page ``idx[b]`` of the (P, K, r)/(P, r, N)
    page stacks — y[b] = x[b] @ W + s*(x[b] @ A[idx[b]]) @ B[idx[b]].
    The vmap over rows collapses to one multi-adapter kernel call
    (pallas/interpret) or the gathered batched mirror ('jnp'); either way
    the frozen-W GEMM runs once for the whole batch."""
    fn = _lora_multi_fn(float(scale), get_backend())
    return jax.vmap(fn, in_axes=(0, 0, None, None, None))(
        x, idx, w, a_stack, b_stack)


# ---------------------------------------------------------------------------
# RWKV6 WKV recurrence (fresh-state training path)
# ---------------------------------------------------------------------------

@jax.custom_jvp
def wkv6_mix(r, k, v, w, u):
    """y = WKV6(r, k, v, w, u) from a fresh state — the training-path
    sequence mixer. r,k,v,w: (B,S,H,hd) fp32; u: (H,hd). The primal is the
    jnp scan mirror (bit-identical to models/ssm.py::wkv6_recurrence); the
    JVP rule lowers tangents to ``wkv6_scan_mt_tangents`` on kernel
    backends inside ``forward_ad_region()``."""
    return wkv6_scan_ref(r, k, v, w, u)[0]


@functools.partial(wkv6_mix.defjvp, symbolic_zeros=True)
def _wkv6_mix_jvp(primals, tangents):
    r, k, v, w, u = primals
    rd, kd, vd, wd, ud = tangents
    backend = get_backend()
    if backend in ("pallas", "interpret") and in_forward_ad_region():
        # primal (tangent-independent, so linearize still splits the rule):
        # the compiled chunked kernel on TPU — the jnp scan pays the
        # per-token HBM round-trip of the (hd,hd) state the kernel exists to
        # remove; under the interpreter keep the fast XLA scan (the kernel
        # dataflow is already exercised by the tangent route)
        if backend == "pallas":
            y = wkv6_scan(r, k, v, w, u, interpret=False)
        else:
            y = wkv6_scan_ref(r, k, v, w, u)[0]
        has_ud = not isinstance(ud, SymbolicZero)
        fn = _wkv6_tangent_fn(has_ud, backend == "interpret")
        args = (r, k, v, w, u, _materialize(rd, r), _materialize(kd, k),
                _materialize(vd, v), _materialize(wd, w))
        if has_ud:
            args += (ud,)
        return y, fn(*args)

    def f(r_, k_, v_, w_, u_):
        return wkv6_scan_ref(r_, k_, v_, w_, u_)[0]

    return jax.jvp(f, primals, (
        _materialize(rd, r), _materialize(kd, k), _materialize(vd, v),
        _materialize(wd, w), _materialize(ud, u)))


# ---------------------------------------------------------------------------
# Mamba2 state recurrence (fresh-state training path)
# ---------------------------------------------------------------------------

@jax.custom_jvp
def mamba2_mix(xdt, bmat, cmat, decay):
    """y = Mamba2 recurrence from a fresh state — the training-path
    sequence mixer. xdt: (B,S,H,hd) fp32 (the dt-premultiplied input
    xh * dt); bmat,cmat: (B,S,N); decay: (B,S,H). The primal is the jnp
    scan mirror (bit-identical to the scan inside models/ssm.py::
    mamba2_mix); the JVP rule lowers tangents to
    ``mamba2_scan_mt_tangents`` on kernel backends inside
    ``forward_ad_region()``."""
    return mamba2_scan_ref(xdt, bmat, cmat, decay)[0]


@functools.partial(mamba2_mix.defjvp, symbolic_zeros=True)
def _mamba2_mix_jvp(primals, tangents):
    xdt, bm, cm, dec = primals
    xd, bd, cd, dd = tangents
    backend = get_backend()
    if backend in ("pallas", "interpret") and in_forward_ad_region():
        # primal (tangent-independent, so linearize still splits the rule):
        # the compiled state-walk kernel on TPU — the jnp scan pays the
        # per-token HBM round-trip of the (hd,N) state; under the
        # interpreter keep the fast XLA scan (the kernel dataflow is
        # already exercised by the tangent route)
        if backend == "pallas":
            y = mamba2_ops.mamba2_scan(xdt, bm, cm, dec, interpret=False)
        else:
            y = mamba2_scan_ref(xdt, bm, cm, dec)[0]
        fn = _mamba2_tangent_fn(backend == "interpret")
        return y, fn(xdt, bm, cm, dec, _materialize(xd, xdt),
                     _materialize(bd, bm), _materialize(cd, cm),
                     _materialize(dd, dec))

    def f(x_, b_, c_, d_):
        return mamba2_scan_ref(x_, b_, c_, d_)[0]

    return jax.jvp(f, primals, (
        _materialize(xd, xdt), _materialize(bd, bm), _materialize(cd, cm),
        _materialize(dd, dec)))


# ---------------------------------------------------------------------------
# Sliding-window attention (prefill/training path)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_jvp, nondiff_argnums=(3,))
def swa_attend(q, k, v, window):
    """Causal (sliding-window) GQA attention, kernel layout: q (B,H,S,hd);
    k,v (B,KV,S,hd), contiguous query-head groups. The primal is the grouped
    jnp mirror (no repeated K/V); the JVP rule lowers tangents to
    ``swa_attention_mt_tangents`` on kernel backends inside
    ``forward_ad_region()``."""
    return swa_attention_gqa_ref(q, k, v, window=window)


@functools.partial(swa_attend.defjvp, symbolic_zeros=True)
def _swa_attend_jvp(window, primals, tangents):
    q, k, v = primals
    qd, kd, vd = tangents
    backend = get_backend()
    if backend in ("pallas", "interpret") and in_forward_ad_region():
        # primal via the flash kernel on TPU: the grouped jnp mirror
        # materializes the (S, S) score tensor, which would make every
        # estimate's primal quadratic in memory; under the interpreter the
        # mirror is the fast CPU path
        if backend == "pallas":
            y = swa_attention(q, k, v, window=window, interpret=False)
        else:
            y = swa_attention_gqa_ref(q, k, v, window=window)
        fn = _swa_tangent_fn(window, backend == "interpret")
        return y, fn(q, k, v, _materialize(qd, q), _materialize(kd, k),
                     _materialize(vd, v))

    def f(q_, k_, v_):
        return swa_attention_gqa_ref(q_, k_, v_, window=window)

    return jax.jvp(f, primals, (
        _materialize(qd, q), _materialize(kd, k), _materialize(vd, v)))


# ---------------------------------------------------------------------------
# Cotangent-known contraction route: <gy, ydot_t> without tangent outputs
# ---------------------------------------------------------------------------

def _vdot32(a, b):
    return jnp.vdot(a.astype(jnp.float32), b.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _lora_contract_fn(scale: float, has_xd: bool, backend: str):
    """Single-tangent <gy, lora-ydot>, custom-vmapped so K stacked tangents
    lower to ONE ``lora_dual_mt_jvps`` epilogue call (T=K) — the fused
    contraction kernel on pallas/interpret backends, the reassociated
    einsum mirror on 'jnp'. Neither ever materializes a (K, M, N) tangent
    stack."""
    kw = dict(scale=scale, impl="kernel" if backend in ("pallas", "interpret")
              else "reassoc", interpret=backend == "interpret")
    if has_xd:
        def base(gy, x, w, a, b, xd, ad, bd):
            return lora_dual_mt_jvps(x, w, a, ad[None], b, bd[None], gy,
                                     xdots=xd[None], **kw)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, gy, x, w, a, b, xd, ad, bd):
            if not any(in_batched[:5]):
                xd, ad, bd = _stack_tangents(axis_size, (xd, ad, bd),
                                             in_batched[5:])
                return lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, xdots=xd,
                                         **kw), True
            return _vmap_fallback(axis_size, in_batched,
                                  (gy, x, w, a, b, xd, ad, bd), base)
    else:
        def base(gy, x, w, a, b, ad, bd):
            return lora_dual_mt_jvps(x, w, a, ad[None], b, bd[None], gy,
                                     **kw)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, gy, x, w, a, b, ad, bd):
            if not any(in_batched[:5]):
                ad, bd = _stack_tangents(axis_size, (ad, bd), in_batched[5:])
                return lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, **kw), True
            return _vmap_fallback(axis_size, in_batched,
                                  (gy, x, w, a, b, ad, bd), base)
    return f


def lora_jvp_contract(gy, x, w, a, b, ad, bd, xd=None, *, scale=1.0):
    """jvp partial of a LoRA projection site against a known cotangent:
    <gy, ydot> for tangents (xd, ad, bd) — ``xd=None`` statically removes
    the input-tangent terms (the projection is the first perturbed unit).
    Under the batched estimator's vmap this lowers to ONE ``_jvps``
    epilogue kernel call with no (K, M, N) tangent output."""
    fn = _lora_contract_fn(float(scale), xd is not None, get_backend())
    if xd is not None:
        return fn(gy, x, w, a, b, xd, ad, bd)
    return fn(gy, x, w, a, b, ad, bd)


@functools.lru_cache(maxsize=None)
def _wkv6_contract_fn(has_ud: bool, backend: str):
    """Single-tangent <gy, wkv6-ydot>, custom-vmapped onto
    ``wkv6_scan_mt_jvps`` (contraction inside the chunked walk) on
    kernel backends; jnp mirror materializes-and-contracts (XLA fuses)."""
    if backend not in ("pallas", "interpret"):
        def jnp_base(gy, r, k, v, w, u, rd, kd, vd, wd, *maybe_ud):
            tangents = (rd, kd, vd, wd,
                        maybe_ud[0] if maybe_ud else jnp.zeros_like(u))
            yd = jax.jvp(lambda *p: wkv6_scan_ref(*p)[0], (r, k, v, w, u),
                         tangents)[1]
            return _vdot32(gy, yd)
        return jnp_base

    interpret = backend == "interpret"
    if has_ud:
        def base(gy, r, k, v, w, u, rd, kd, vd, wd, ud):
            return wkv6_scan_mt_jvps(r, k, v, w, u, rd[None], kd[None],
                                     vd[None], wd[None], gy, ud[None],
                                     interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, gy, r, k, v, w, u, rd, kd, vd, wd,
                  ud):
            if not any(in_batched[:6]):
                rd, kd, vd, wd, ud = _stack_tangents(
                    axis_size, (rd, kd, vd, wd, ud), in_batched[6:])
                return wkv6_scan_mt_jvps(r, k, v, w, u, rd, kd, vd, wd, gy,
                                         ud, interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (gy, r, k, v, w, u, rd, kd, vd, wd, ud),
                                  base)
    else:
        def base(gy, r, k, v, w, u, rd, kd, vd, wd):
            return wkv6_scan_mt_jvps(r, k, v, w, u, rd[None], kd[None],
                                     vd[None], wd[None], gy,
                                     interpret=interpret)[0]

        f = custom_vmap(base)

        @f.def_vmap
        def _rule(axis_size, in_batched, gy, r, k, v, w, u, rd, kd, vd, wd):
            if not any(in_batched[:6]):
                rd, kd, vd, wd = _stack_tangents(
                    axis_size, (rd, kd, vd, wd), in_batched[6:])
                return wkv6_scan_mt_jvps(r, k, v, w, u, rd, kd, vd, wd, gy,
                                         interpret=interpret), True
            return _vmap_fallback(axis_size, in_batched,
                                  (gy, r, k, v, w, u, rd, kd, vd, wd), base)
    return f


def wkv6_jvp_contract(gy, r, k, v, w, u, rd, kd, vd, wd, ud=None):
    """jvp partial of a WKV6 mixer site against a known cotangent:
    <gy, ydot>. Batched tangents lower to ONE ``wkv6_scan_mt_jvps``
    epilogue call — no (K, B, S, H, hd) tangent output."""
    fn = _wkv6_contract_fn(ud is not None, get_backend())
    args = (gy, r, k, v, w, u, rd, kd, vd, wd)
    if ud is not None:
        args += (ud,)
    return fn(*args)


@functools.lru_cache(maxsize=None)
def _mamba2_contract_fn(backend: str):
    """Single-tangent <gy, mamba2-ydot>, custom-vmapped onto
    ``mamba2_scan_mt_jvps`` (per-token contraction inside the state walk) on
    kernel backends; jnp mirror materializes-and-contracts (XLA fuses)."""
    if backend not in ("pallas", "interpret"):
        def jnp_base(gy, xdt, bm, cm, dec, xd, bd, cd, dd):
            yd = jax.jvp(lambda *p: mamba2_scan_ref(*p)[0],
                         (xdt, bm, cm, dec), (xd, bd, cd, dd))[1]
            return _vdot32(gy, yd)
        return jnp_base

    interpret = backend == "interpret"

    def base(gy, xdt, bm, cm, dec, xd, bd, cd, dd):
        return mamba2_ops.mamba2_scan_mt_jvps(
            xdt, bm, cm, dec, xd[None], bd[None], cd[None], dd[None], gy,
            interpret=interpret)[0]

    f = custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, gy, xdt, bm, cm, dec, xd, bd, cd, dd):
        if not any(in_batched[:5]):
            xd, bd, cd, dd = _stack_tangents(axis_size, (xd, bd, cd, dd),
                                             in_batched[5:])
            return mamba2_ops.mamba2_scan_mt_jvps(
                xdt, bm, cm, dec, xd, bd, cd, dd, gy,
                interpret=interpret), True
        return _vmap_fallback(axis_size, in_batched,
                              (gy, xdt, bm, cm, dec, xd, bd, cd, dd), base)
    return f


def mamba2_jvp_contract(gy, xdt, bm, cm, dec, xd, bd, cd, dd):
    """jvp partial of a Mamba2 mixer site against a known cotangent:
    <gy, ydot>. Batched tangents lower to ONE ``mamba2_scan_mt_jvps``
    epilogue call — no (K, B, S, H, hd) tangent output."""
    return _mamba2_contract_fn(get_backend())(gy, xdt, bm, cm, dec, xd, bd,
                                              cd, dd)


@functools.lru_cache(maxsize=None)
def _swa_contract_fn(window, backend: str):
    """Single-tangent <gy, swa-outd>, custom-vmapped onto
    ``swa_attention_mt_jvps`` (per-query-block contraction at the end of
    the online-softmax walk) on kernel backends."""
    if backend not in ("pallas", "interpret"):
        def jnp_base(gy, q, k, v, qd, kd, vd):
            outd = jax.jvp(
                lambda q_, k_, v_: swa_attention_gqa_ref(q_, k_, v_,
                                                         window=window),
                (q, k, v), (qd, kd, vd))[1]
            return _vdot32(gy, outd)
        return jnp_base

    interpret = backend == "interpret"

    def base(gy, q, k, v, qd, kd, vd):
        return swa_attention_mt_jvps(q, k, v, qd[None], kd[None], vd[None],
                                     gy, window=window,
                                     interpret=interpret)[0]

    f = custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, gy, q, k, v, qd, kd, vd):
        if not any(in_batched[:4]):
            qd, kd, vd = _stack_tangents(axis_size, (qd, kd, vd),
                                         in_batched[4:])
            return swa_attention_mt_jvps(q, k, v, qd, kd, vd, gy,
                                         window=window,
                                         interpret=interpret), True
        return _vmap_fallback(axis_size, in_batched,
                              (gy, q, k, v, qd, kd, vd), base)
    return f


def swa_jvp_contract(gy, q, k, v, qd, kd, vd, window):
    """jvp partial of an SWA attention site against a known cotangent:
    <gy, outd>. Batched tangents lower to ONE ``swa_attention_mt_jvps``
    epilogue call — no (K, B, H, S, hd) tangent output."""
    return _swa_contract_fn(window, get_backend())(gy, q, k, v, qd, kd, vd)
