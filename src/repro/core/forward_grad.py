"""Forward-mode AD gradient estimation (paper §2, Eq. 1-3).

    jvp      = J_f(w) · v           — directional derivative along v
    grad_est = jvp * v              — unbiased estimator of ∇f for v~N(0,I)

K>1 perturbations are averaged (paper's ablation Fig. 5a). Perturbations are
regenerated from scalar seeds with ``jax.random.fold_in`` chains so the
server can rebuild any client's v exactly (per-iteration communication mode
sends only the jvp scalar back — Table 2).

Tangent-axis contract (this module's batched engine)
----------------------------------------------------
K perturbations are stacked on a leading *tangent axis*: a stacked
perturbation tree has leaves of shape ``(K,) + leaf.shape`` and the jvp
vector has shape ``(K,)``. The default path linearizes the loss once
(``jax.linearize``) and evaluates all K tangents through the linear map with
``jax.vmap`` — the frozen-base primal is computed ONCE per estimate instead
of K times (the paper's §5.3 "column-by-column jvp" overhead). Ops whose
inputs carry no tangent stay unbatched under vmap, so only tangent-carrying
intermediates gain the K axis.

On kernel backends the vmap does not stop at batched jnp ops: the dispatch
layer (kernels/dispatch.py) registers custom batching rules so that
vmap-of-tangents through a LoRA projection, an RWKV6 recurrence, or an SWA
attention block lowers DIRECTLY to the corresponding multi-tangent Pallas
kernel (``lora_dual_mt_tangents`` / ``wkv6_scan_mt_tangents`` /
``swa_attention_mt_tangents``) — the same leading-K tangent axis becomes
the kernel's T axis, and one pass over the primal operands serves all K
tangents in VMEM.

``tangent_batch`` trades that amortization against tangent-intermediate
memory (each tangent-carrying activation is K× wider):

    None / >=K  one batched pass (default; max primal amortization)
    1           the sequential fori_loop of full jax.jvp passes — zero
                stacked tangents, primal recomputed per perturbation
                (memory-constrained clients; the seed behaviour)
    1<b<K       ceil(K/b) groups scanned sequentially, b tangents per pass;
                K is padded to a multiple of b with masked-out tangents so
                ONE scanned trace covers everything (no re-traced remainder
                tail), and both the gradient accumulator and the jvp buffer
                ride the scan carry — donated in-place by XLA, so only one
                group of stacked tangents is ever live

Fused contraction (cotangent-known epilogues)
---------------------------------------------
``fused_contraction=True`` with a ``SplitLoss`` — a loss that declares its
final mixer site, ``loss(p) = post(site(*args), ctx, p)`` with
``(args, ctx) = pre(p)`` — exploits that everything downstream of the site
is cheap: the post-head is reversed ONCE (jax.vjp over the head only — no
mixer activations stored) for the cotangents (gy, g_ctx, g_p), and each
tangent's site contribution <gy, ydot_t> is computed by the dispatch
layer's ``*_jvp_contract`` ops, whose custom-vmap lowering picks the
``*_mt_jvps`` contraction-epilogue kernels — the K tangent outputs of the
site are contracted blockwise in VMEM and NEVER written to HBM. The jvp
scalars equal the standard route's up to float reassociation of the
contraction.

Phase scopes
------------
The estimator's work is named in the compiled program's HLO metadata
(``jax.named_scope``): ``spry.primal`` is the once-per-estimate linearize
(on the fused route also the site primal, the post-head vjp and ``zw``);
``spry.tangents`` is the evaluation of the K tangents through the linear
map. The sequential and K=1 routes run primal and tangent in one
``jax.jvp``, which is scoped ``spry.tangents`` whole. The linear map from
``jax.linearize`` replays, inside the scope where it is applied, the name
stack it was built under, so its ops read ``spry.tangents/...spry.primal``:
an op under ``spry.tangents`` is tangent work whatever scope follows. The
scopes change metadata only, never the compiled buffers or numbers.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.dispatch import forward_ad_region
from repro.utils.pytree import normal_like


def masked_perturbation(key, peft, mask_tree=None):
    """v ~ N(0, I) over the trainable tree, zeroed outside the client's
    assigned units (SPRY's weight splitting)."""
    v = normal_like(key, peft, dtype=jnp.float32)
    if mask_tree is not None:
        v = jax.tree.map(lambda vi, m: vi * m, v, mask_tree)
    return v


def stacked_perturbations(key, peft, indices, mask_tree=None):
    """Perturbations for ``fold_in(key, i) for i in indices`` stacked on a
    leading tangent axis. Bit-identical per index to ``masked_perturbation``
    (vmap of the PRNG chain is deterministic), which is what lets the server
    rebuild the client's exact tangents from the scalar seed."""
    return jax.vmap(
        lambda i: masked_perturbation(jax.random.fold_in(key, i), peft,
                                      mask_tree))(indices)


def _combine(jvps, vs, k_total):
    """g = (1/K) Σ_i jvps[i] · vs[i] — the estimator average, contracted over
    the tangent axis. Shared by the client estimator and the server-side
    reconstruction so the two are bit-identical (same ops, same inputs)."""
    return jax.tree.map(
        lambda v: jnp.tensordot(jvps, v, axes=[[0], [0]]) / k_total, vs)


def _tangents(tangent_map, vs):
    """The stacked tangents ``vs`` through the linear map, one vmap."""
    with jax.named_scope("spry.tangents"):
        return jax.vmap(tangent_map)(vs)


# ---------------------------------------------------------------------------
# Split losses: a declared final mixer site for the fused-contraction route
# ---------------------------------------------------------------------------

class SplitLoss:
    """A loss with a declared final ("epilogue-eligible") mixer site:

        loss(p) = post(site(*site_args), ctx, p),  (site_args, ctx) = pre(p)

    ``kind`` selects the site op and its tangent-contraction epilogue:

        'lora'    site_args = (x, w, a, b), static ``scale``
                  -> dispatch.lora_proj / lora_jvp_contract
        'wkv6'    site_args = (r, k, v, w, u)
                  -> dispatch.wkv6_mix / wkv6_jvp_contract
        'swa'     site_args = (q, k, v), static ``window``
                  -> dispatch.swa_attend / swa_jvp_contract
        'mamba2'  site_args = (xdt, bmat, cmat, decay)
                  -> dispatch.mamba2_mix / mamba2_jvp_contract

    ``ctx`` is any tangent-carrying side output of ``pre`` the post-head
    also needs (residual streams, aux losses; None if none). Calling the
    object evaluates the composition through the normally-dispatched site
    op, so it is a drop-in ``loss_fn``; ``forward_gradient(...,
    fused_contraction=True)`` additionally exploits the split (see module
    docstring).

    ``x_has_tangent=False`` (lora only) declares that x does NOT depend on
    the trainable tree — the projection is the first perturbed unit — which
    statically removes the input-tangent GEMMs from the epilogue kernel.

    ``site_fn`` optionally overrides the kind-based site PRIMAL (the
    contraction epilogue is still selected by ``kind``): the registry's
    full-model split losses pass the family's backend-gated mixer here so
    the SplitLoss traces exactly the same program as the plain loss closure
    (bitwise-equal values on every backend).
    """

    def __init__(self, pre: Callable, kind: str, post: Callable, *,
                 scale: float = 1.0, window: Optional[int] = None,
                 x_has_tangent: bool = True, site_fn: Optional[Callable] = None):
        if kind not in ("lora", "wkv6", "swa", "mamba2"):
            raise ValueError(f"unknown site kind {kind!r}")
        self.pre = pre
        self.kind = kind
        self.post = post
        self.scale = scale
        self.window = window
        self.x_has_tangent = x_has_tangent
        self.site_fn = site_fn

    def site(self, args):
        if self.site_fn is not None:
            return self.site_fn(args)
        if self.kind == "lora":
            return dispatch.lora_proj(*args, self.scale)
        if self.kind == "wkv6":
            return dispatch.wkv6_mix(*args)
        if self.kind == "mamba2":
            return dispatch.mamba2_mix(*args)
        return dispatch.swa_attend(*args, self.window)

    def __call__(self, p):
        args, ctx = self.pre(p)
        return self.post(self.site(args), ctx, p)


def _tree_vdot(g, t):
    """Σ_leaves <g, t> in fp32 (0.0 for empty trees)."""
    leaves = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.vdot(a.astype(jnp.float32),
                              b.astype(jnp.float32)), g, t))
    return sum(leaves) if leaves else jnp.float32(0.0)


@jax.named_scope("spry.primal")
def fused_linearize(loss_fn: SplitLoss, peft32):
    """(loss, jvp_of) for the fused-contraction route.

    Linearizes ``pre`` once (forward-mode, inside the kernel AD region),
    runs the site primal, reverses the post-head ONCE for the cotangents,
    and returns ``jvp_of(v)`` whose site term contracts in-kernel. Under
    ``jax.vmap`` the K site contributions lower to ONE ``*_mt_jvps``
    epilogue call — no (K, ..., N) tangent output exists at the site."""
    with forward_ad_region():
        (site_args, ctx), pre_lin = jax.linearize(loss_fn.pre, peft32)
        # site primal evaluated in the SAME trace context as the standard
        # route's linearize, so backend-gated site_fns (the registry's
        # model mixers) pick the same branch on both routes — loss bitwise
        y = loss_fn.site(site_args)
    loss, post_vjp = jax.vjp(loss_fn.post, y, ctx, peft32)
    gy, g_ctx, g_p = post_vjp(jnp.ones_like(loss))

    zw = None
    if loss_fn.kind == "lora":
        # frozen-W completeness term <gy, x @ wd_t> = <xᵀgy, wd_t>: the
        # (K_in, N) factor is primal-only, computed once outside the vmap
        # (wd_t is exact zeros in SPRY — W is frozen — and folds away)
        x, w = site_args[0], site_args[1]
        zw = jnp.einsum("...k,...n->kn", x.astype(jnp.float32),
                        gy.astype(jnp.float32))

    def jvp_of(v):
        argdots, ctxdot = pre_lin(v)
        if loss_fn.kind == "lora":
            x, w, a, b = site_args
            xd, wd, ad, bd = argdots
            val = dispatch.lora_jvp_contract(
                gy, x, w, a, b, ad, bd,
                xd=xd if loss_fn.x_has_tangent else None,
                scale=loss_fn.scale)
            val = val + _tree_vdot(zw, wd)
        elif loss_fn.kind == "wkv6":
            val = dispatch.wkv6_jvp_contract(gy, *site_args, *argdots)
        elif loss_fn.kind == "mamba2":
            val = dispatch.mamba2_jvp_contract(gy, *site_args, *argdots)
        else:
            val = dispatch.swa_jvp_contract(gy, *site_args, *argdots,
                                            loss_fn.window)
        return val + _tree_vdot(g_ctx, ctxdot) + _tree_vdot(g_p, v)

    return loss, jvp_of


# losses already warned about once when fused_contraction was requested but
# the loss declares no final mixer site. Keyed by the function's definition
# site (code object location), not its __name__: distinct lambdas/partials
# each warn once, while per-trace re-creations of the same closure do not.
_warned_unsplit_losses: set = set()


def _unsplit_key(loss_fn):
    fn = getattr(loss_fn, "func", loss_fn)       # unwrap functools.partial
    code = getattr(fn, "__code__", None)
    if code is not None:
        return (code.co_filename, code.co_firstlineno)
    return (type(fn).__module__, type(fn).__qualname__)


def _warn_unsplit_fallback(loss_fn):
    fn = getattr(loss_fn, "func", loss_fn)
    name = (getattr(fn, "__name__", None) or getattr(loss_fn, "__name__", None)
            or type(loss_fn).__name__)
    key = _unsplit_key(loss_fn)
    if key in _warned_unsplit_losses:
        return
    _warned_unsplit_losses.add(key)
    warnings.warn(
        f"fused_contraction=True was requested but loss {name!r} does not "
        f"declare a final mixer site (not a SplitLoss); taking the standard "
        f"materializing tangent route instead. Build the loss with "
        f"repro.models.registry.get_loss_fn(task, split=True) to run the "
        f"fused jvp-contraction epilogues.",
        stacklevel=3)


def forward_gradient(loss_fn, peft, key, k_perturbations=1, mask_tree=None,
                     jvp_clip=None, tangent_batch=None,
                     fused_contraction=False):
    """Forward-gradient estimate of ∇_peft loss_fn.

    Returns (loss, grad_estimate, jvps (K,)). ``loss_fn`` must be a function
    of the peft tree only (base weights closed over).

    ``tangent_batch`` — see module docstring. The batched paths and the
    sequential path are numerically equivalent per seed (same perturbations,
    same jvp values) up to float reassociation of the K-average.

    ``fused_contraction`` — when True AND ``loss_fn`` is a ``SplitLoss``
    (declares its final mixer site), the site's K tangent outputs are
    contracted against the post-head cotangent inside the kernel instead of
    being materialized (see module docstring). A plain callable loss_fn
    keeps the standard route with a one-time ``UserWarning`` naming the
    loss and the route taken (the registry's ``get_loss_fn(task,
    split=True)`` builders produce fused-capable losses for every family).

    ``jvp_clip`` (beyond-paper stabiliser): clamp the jvp scalar to
    [-c, c] before forming jvp*v — bounds the update magnitude of outlier
    perturbations (a biased but much lower-variance estimator; off by
    default, matches the paper exactly when None).
    """
    peft32 = jax.tree.map(lambda x: x.astype(jnp.float32), peft)
    K = int(k_perturbations)
    tb = K if tangent_batch is None else max(1, min(int(tangent_batch), K))
    fused = fused_contraction and isinstance(loss_fn, SplitLoss)
    if fused_contraction and not fused:
        _warn_unsplit_fallback(loss_fn)

    def clip(jvps):
        if jvp_clip is not None:
            return jnp.clip(jvps, -jvp_clip, jvp_clip)
        return jvps

    if K == 1 and not fused:
        # no tangent stacking needed — single dual-number pass
        v = masked_perturbation(jax.random.fold_in(key, 0), peft32, mask_tree)
        with forward_ad_region(), jax.named_scope("spry.tangents"):
            loss, jvp = jax.jvp(loss_fn, (peft32,), (v,))
        jvps = clip(jnp.reshape(jvp, (1,)))
        vs = jax.tree.map(lambda x: x[None], v)
        return loss, _combine(jvps, vs, 1), jvps

    if tb == 1 and not fused:
        # sequential fallback: one full jax.jvp pass per perturbation — no
        # stacked tangents and in-loop g accumulation (bounded memory), the
        # primal recomputed K times (the seed behaviour)
        def one(i, carry):
            g, jvps, loss_acc = carry
            ki = jax.random.fold_in(key, i)
            v = masked_perturbation(ki, peft32, mask_tree)
            with forward_ad_region(), jax.named_scope("spry.tangents"):
                loss, jvp = jax.jvp(loss_fn, (peft32,), (v,))
            if jvp_clip is not None:
                jvp = jnp.clip(jvp, -jvp_clip, jvp_clip)
            g = jax.tree.map(lambda gi, vi: gi + jvp * vi, g, v)
            return g, jvps.at[i].set(jvp), loss_acc + loss

        g0 = jax.tree.map(jnp.zeros_like, peft32)
        g, jvps, loss = jax.lax.fori_loop(
            0, K, one,
            (g0, jnp.zeros((K,), jnp.float32), jnp.float32(0.0)))
        scale = 1.0 / K
        return loss * scale, jax.tree.map(lambda x: x * scale, g), jvps

    # batched: linearize once (one primal), push tangent groups through the
    # linear map with vmap — stacked-tangent jvp. (forward_ad_region lets
    # the dispatch layer lower LoRA tangents to the fused Pallas kernel —
    # the tangent jaxpr is fixed here at trace time, so later vmap replays
    # of tangent_map inherit it.) On the fused route the site tangents are
    # contracted in-kernel against the post-head cotangent instead.
    if fused:
        loss, tangent_map = fused_linearize(loss_fn, peft32)
    else:
        with forward_ad_region(), jax.named_scope("spry.primal"):
            loss, tangent_map = jax.linearize(loss_fn, peft32)

    if tb >= K:
        vs = stacked_perturbations(key, peft32, jnp.arange(K), mask_tree)
        jvps = clip(_tangents(tangent_map, vs))
        return loss, _combine(jvps, vs, K), jvps

    # chunked: ceil(K/tb) groups of tb tangents, scanned sequentially
    # (bounds the stacked-tangent memory to tb× while still amortizing
    # inside a group). K is padded to a multiple of tb with masked-out
    # tangents so ONE scanned trace covers everything — no re-traced
    # remainder tail — and the padded lanes contribute exact zeros (their
    # jvps are zeroed before the combine). Both accumulators ride the scan
    # carry, which XLA donates in-place: only one group of stacked
    # perturbations is ever live.
    n_groups = -(-K // tb)
    g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), peft32)

    def scan_body(carry, start):
        g_acc, jvps_acc = carry
        idx = start + jnp.arange(tb)
        vs_g = stacked_perturbations(key, peft32, idx, mask_tree)
        live = (idx < K).astype(jnp.float32)
        jvps_g = clip(_tangents(tangent_map, vs_g)) * live
        g_acc = jax.tree.map(jnp.add, g_acc, _combine(jvps_g, vs_g, K))
        jvps_acc = jax.lax.dynamic_update_slice(jvps_acc, jvps_g, (start,))
        return (g_acc, jvps_acc), None

    (g, jvps_pad), _ = jax.lax.scan(
        scan_body, (g0, jnp.zeros((n_groups * tb,), jnp.float32)),
        jnp.arange(n_groups) * tb)
    return loss, g, jvps_pad[:K]


def reconstruct_gradient(peft_template, key, jvps, mask_tree=None):
    """Server-side gradient reconstruction from jvp scalars + the shared seed
    (per-iteration communication mode, paper §3.2). Regenerates the stacked
    perturbations and applies the same ``_combine`` contraction as the
    client-side estimator, so the rebuild is bit-identical to the client's
    estimate and its trace stays O(1) in K — enforced by
    tests/test_forward_grad.py."""
    K = jvps.shape[0]
    template32 = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), peft_template)
    vs = stacked_perturbations(key, template32, jnp.arange(K), mask_tree)
    return _combine(jvps, vs, K)
