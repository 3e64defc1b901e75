"""Recurrent sequence-mixing blocks: RWKV6 ("Finch") and Mamba2.

Both are implemented as exact sequential recurrences with ``lax.scan`` over
time — O(1) state, which is what makes the long_500k decode shape lower for
these families. The TPU fast path for the RWKV6 recurrence is the
kernels/wkv6_scan Pallas kernel (chunk-parallel: sub-chunks on the MXU, the
state carried across them in VMEM); this module is
the semantics-defining reference the kernel is tested against.

RWKV6 (data-dependent decay, the paper's headline Finch feature):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T        (per head, S in R^{hd x hd})
    y_t = r_t^T (S_{t-1} + (u * k_t) v_t^T)
with w_t = exp(-exp(w0 + lora(x_t))) in (0,1) elementwise.

Mamba2 (scalar-per-head decay):
    h_t = exp(-softplus(a) * dt_t) h_{t-1} + dt_t * (x_t outer B_t)
    y_t = h_t C_t + D * x_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.mamba2_scan.ref import mamba2_scan_ref
from repro.models.common import dense_init, maybe_lora, proj


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

def rwkv6_params(cfg, key, layers=None):
    d = cfg.d_model
    hd = cfg.ssm.head_dim
    H = d // hd
    stack = (layers,) if layers else ()
    names = ["wr", "wk", "wv", "wg", "wo", "w_lora_a", "w_lora_b",
             "mu", "w0", "u", "ln_w", "ln_b",
             "cm_wr", "cm_wk", "cm_wv"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    r_decay = 64  # decay-LoRA rank (Finch uses low-rank data-dependent decay)
    p = {
        # time-mix projections
        "wr": dense_init(keys["wr"], stack + (d, d), dtype=cfg.dtype),
        "wk": dense_init(keys["wk"], stack + (d, d), dtype=cfg.dtype),
        "wv": dense_init(keys["wv"], stack + (d, d), dtype=cfg.dtype),
        "wg": dense_init(keys["wg"], stack + (d, d), dtype=cfg.dtype),
        "wo": dense_init(keys["wo"], stack + (d, d), dtype=cfg.dtype),
        # data-dependent decay (low-rank)
        "w_lora_a": dense_init(keys["w_lora_a"], stack + (d, r_decay), dtype=cfg.dtype),
        "w_lora_b": dense_init(keys["w_lora_b"], stack + (r_decay, d), dtype=cfg.dtype) * 0.1,
        "w0": jnp.zeros(stack + (d,), jnp.float32) + 0.5,
        # token-shift interpolation factors per projection (r,k,v,g,w)
        "mu": jax.random.uniform(keys["mu"], stack + (5, d), jnp.float32),
        # per-head bonus
        "u": dense_init(keys["u"], stack + (H, hd), dtype=jnp.float32),
        # group norm over heads
        "ln_w": jnp.ones(stack + (d,), jnp.float32),
        "ln_b": jnp.zeros(stack + (d,), jnp.float32),
        # channel-mix
        "cm_wr": dense_init(keys["cm_wr"], stack + (d, d), dtype=cfg.dtype),
        "cm_wk": dense_init(keys["cm_wk"], stack + (d, cfg.d_ff), dtype=cfg.dtype),
        "cm_wv": dense_init(keys["cm_wv"], stack + (cfg.d_ff, d), dtype=cfg.dtype),
    }
    return p


def _token_shift(x, prev):
    """Shift right by one along S; ``prev`` is the carry from decode (B,1,D)
    or zeros for a fresh sequence."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def wkv6_recurrence(r, k, v, w, u, state):
    """Sequential WKV scan. r,k,v,w: (B,S,H,hd); u: (H,hd);
    state: (B,H,hd,hd). Returns (y: (B,S,H,hd), new_state)."""
    def step(s, xs):
        rt, kt, vt, wt = xs                                   # (B,H,hd)
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)              # (B,H,hd,hd)
        yt = jnp.einsum("bhi,bhij->bhj", rt, s + u[None, :, :, None] * kv)
        s = wt[..., None] * s + kv
        return s, yt

    xs = jax.tree.map(lambda a: a.transpose(1, 0, 2, 3), (r, k, v, w))  # (S,B,H,hd)
    state, ys = jax.lax.scan(step, state, xs)
    return ys.transpose(1, 0, 2, 3), state


def rwkv6_site_args(cfg, p, x, peft_layer=None, lora_scale=1.0,
                    shift_prev=None):
    """Time-mix projections up to the WKV recurrence: the mixer-site
    operands ((r, k, v, w) (B,S,H,hd) fp32 + u (H,hd)) plus the gate stream
    ``g`` the post-mixer tail needs. Shared by ``rwkv6_time_mix`` and the
    rwkv split forward (the recurrence is the declared fused-contraction
    site there)."""
    B, S, D = x.shape
    hd = cfg.ssm.head_dim
    H = D // hd
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, 1, D), x.dtype)
    xs = _token_shift(x, prev)
    mu = p["mu"]                                             # (5, D)

    def lerp(i):
        return (x + (xs - x) * mu[i]).astype(x.dtype)

    r = proj(lerp(0), p["wr"], lora=maybe_lora(peft_layer, "wr"), lora_scale=lora_scale)
    k = proj(lerp(1), p["wk"], lora=maybe_lora(peft_layer, "wk"), lora_scale=lora_scale)
    v = proj(lerp(2), p["wv"], lora=maybe_lora(peft_layer, "wv"), lora_scale=lora_scale)
    g = proj(lerp(3), p["wg"], lora=maybe_lora(peft_layer, "wg"), lora_scale=lora_scale)
    # data-dependent decay in fp32, in (0,1)
    dw = (lerp(4) @ p["w_lora_a"]) @ p["w_lora_b"]
    w = jnp.exp(-jnp.exp(p["w0"] + dw.astype(jnp.float32)))   # (B,S,D)

    hsplit = lambda t: t.reshape(B, S, H, hd)
    return (hsplit(r).astype(jnp.float32), hsplit(k).astype(jnp.float32),
            hsplit(v).astype(jnp.float32), hsplit(w), p["u"]), g


def rwkv6_finish(cfg, p, y, g, out_dtype, peft_layer=None, lora_scale=1.0):
    """Group-norm + gate + output projection on the mixer output y
    ((B,S,H,hd) fp32) — the time-mix tail after the WKV recurrence (the
    split forwards' post side)."""
    B, S, H, hd = y.shape
    D = H * hd
    # group-norm per head then gate
    mean = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, D)
    y = (y * p["ln_w"] + p["ln_b"]).astype(out_dtype) * jax.nn.silu(g)
    return proj(y, p["wo"], lora=maybe_lora(peft_layer, "wo"),
                lora_scale=lora_scale)


def wkv6_mixer_site(args):
    """Fresh-state WKV6 recurrence on the ``rwkv6_site_args`` operands with
    the model's backend gating: the dispatched op (multi-tangent kernels
    inside the estimator's forward-AD region) on kernel backends, the exact
    sequential jnp recurrence otherwise. The rwkv split forward declares
    this call as its fused-contraction site."""
    r, k, v, w, u = args
    if dispatch.use_kernel_mixers():
        return dispatch.wkv6_mix(r, k, v, w, u)
    B, _, H, hd = r.shape
    state = jnp.zeros((B, H, hd, hd), jnp.float32)
    return wkv6_recurrence(r, k, v, w, u, state)[0]


def rwkv6_time_mix(cfg, p, x, peft_layer=None, lora_scale=1.0, state=None,
                   shift_prev=None):
    """x: (B,S,D). state: (B,H,hd,hd) or None (zeros). Returns
    (out, new_state, last_x). On the dispatched forward-gradient fast path
    (fresh state inside ``dispatch.use_kernel_mixers()``) new_state is None —
    the estimator's loss closures never consume it."""
    B, S, D = x.shape
    hd = cfg.ssm.head_dim
    H = D // hd
    (r, k, v, w, u), g = rwkv6_site_args(cfg, p, x, peft_layer, lora_scale,
                                         shift_prev)
    if state is None and dispatch.use_kernel_mixers():
        # forward-gradient fast path (fresh state): the dispatched op lowers
        # K stacked tangents to the multi-tangent wkv6 Pallas kernel — one
        # pass of the primal pieces for all K perturbations. The
        # estimator's loss closures discard the carried state, so none is
        # produced here.
        y = dispatch.wkv6_mix(r, k, v, w, u)
        state = None
    else:
        if state is None:
            state = jnp.zeros((B, H, hd, hd), jnp.float32)
        y, state = wkv6_recurrence(r, k, v, w, u, state)
    out = rwkv6_finish(cfg, p, y, g, x.dtype, peft_layer, lora_scale)
    return out, state, x[:, -1:, :]


def rwkv6_channel_mix(cfg, p, x, shift_prev=None):
    B, S, D = x.shape
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, 1, D), x.dtype)
    xs = _token_shift(x, prev)
    r = jax.nn.sigmoid(x @ p["cm_wr"])
    k = jnp.square(jax.nn.relu(xs @ p["cm_wk"]))
    return (r * (k @ p["cm_wv"])).astype(x.dtype), x[:, -1:, :]


# ---------------------------------------------------------------------------
# Mamba2
# ---------------------------------------------------------------------------

def mamba2_params(cfg, key, layers=None):
    d = cfg.d_model
    s = cfg.ssm
    d_inner = s.expand * d
    H = d_inner // s.head_dim
    N = s.state_dim
    names = ["in_proj", "conv", "dt", "wb", "wc", "out"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    stack = (layers,) if layers else ()
    return {
        "in_proj": dense_init(keys["in_proj"], stack + (d, 2 * d_inner), dtype=cfg.dtype),
        "conv_w": dense_init(keys["conv"], stack + (s.conv_kernel, d_inner), dtype=cfg.dtype),
        "w_dt": dense_init(keys["dt"], stack + (d, H), dtype=cfg.dtype),
        "dt_bias": jnp.zeros(stack + (H,), jnp.float32),
        "w_b": dense_init(keys["wb"], stack + (d, N), dtype=cfg.dtype),
        "w_c": dense_init(keys["wc"], stack + (d, N), dtype=cfg.dtype),
        "a_log": jnp.zeros(stack + (H,), jnp.float32),
        "d_skip": jnp.ones(stack + (H,), jnp.float32),
        "out_proj": dense_init(keys["out"], stack + (d_inner, d), dtype=cfg.dtype),
    }


def _causal_depthwise_conv(x, w, conv_state=None):
    """x: (B,S,C), w: (K,C). Returns (y, new_conv_state (B,K-1,C))."""
    K = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([conv_state, x], axis=1)             # (B, S+K-1, C)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return y, xp[:, -(K - 1):]


def mamba2_preamble(cfg, p, x, peft_layer=None, lora_scale=1.0,
                    conv_state=None):
    """in_proj + depthwise conv + dt/B/C/decay streams — ``mamba2_mix`` up
    to the state recurrence. Returns (xh, dt, bmat, cmat, decay, z,
    conv_state). Shared by ``mamba2_mix`` and the hybrid split forward (the
    recurrence over the dt-premultiplied input ``xh * dt`` is the declared
    fused-contraction site there)."""
    B, S, D = x.shape
    s = cfg.ssm
    d_inner = s.expand * D
    hd = s.head_dim
    H = d_inner // hd

    zx = proj(x, p["in_proj"], lora=maybe_lora(peft_layer, "in_proj"),
              lora_scale=lora_scale)
    z, xb = jnp.split(zx, 2, axis=-1)
    xb, conv_state = _causal_depthwise_conv(xb, p["conv_w"], conv_state)
    xb = jax.nn.silu(xb)

    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])                                   # (H,)
    decay = jnp.exp(a[None, None] * dt)                        # (B,S,H)
    bmat = (x @ p["w_b"]).astype(jnp.float32)                  # (B,S,N)
    cmat = (x @ p["w_c"]).astype(jnp.float32)                  # (B,S,N)
    xh = xb.reshape(B, S, H, hd).astype(jnp.float32)
    return xh, dt, bmat, cmat, decay, z, conv_state


def mamba2_finish(cfg, p, y, z, xh, out_dtype, peft_layer=None,
                  lora_scale=1.0):
    """Skip connection + gate + output projection on the mixer output y
    ((B,S,H,hd) fp32) — the mamba2 tail after the state recurrence (the
    split forwards' post side)."""
    B, S, H, hd = y.shape
    d_inner = H * hd
    y = y + p["d_skip"][None, None, :, None] * xh
    y = (y.reshape(B, S, d_inner) * jax.nn.silu(z.astype(jnp.float32))).astype(out_dtype)
    return proj(y, p["out_proj"], lora=maybe_lora(peft_layer, "out_proj"),
                lora_scale=lora_scale)


def mamba2_mixer_site(args):
    """Fresh-state Mamba2 recurrence on (xdt, bmat, cmat, decay) with the
    model's backend gating: the dispatched op on kernel backends, the exact
    jnp scan mirror otherwise (the dt hoist is an exact elementwise
    identity — bit-identical to the in-scan multiply). The hybrid split
    forward declares this call as its fused-contraction site when the final
    layer's last mixer is the mamba2 recurrence."""
    xdt, bmat, cmat, decay = args
    if dispatch.use_kernel_mixers():
        return dispatch.mamba2_mix(xdt, bmat, cmat, decay)
    return mamba2_scan_ref(xdt, bmat, cmat, decay)[0]


def mamba2_mix(cfg, p, x, peft_layer=None, lora_scale=1.0, state=None,
               conv_state=None):
    """x: (B,S,D). state: (B,H,hd,N) or None (zeros). Returns
    (out, state, conv_state). On the dispatched forward-gradient fast path
    (fresh state inside ``dispatch.use_kernel_mixers()``) state is None —
    the estimator's loss closures never consume it."""
    B, S, D = x.shape
    s = cfg.ssm
    d_inner = s.expand * D
    hd = s.head_dim
    H = d_inner // hd
    N = s.state_dim

    xh, dt, bmat, cmat, decay, z, conv_state = mamba2_preamble(
        cfg, p, x, peft_layer, lora_scale, conv_state)

    if state is None and dispatch.use_kernel_mixers():
        # forward-gradient fast path (fresh state): the dispatched op lowers
        # K stacked tangents to the multi-tangent mamba2 Pallas kernel — one
        # primal state walk for all K perturbations. The dt multiplication
        # is hoisted out of the scan (exact elementwise identity); the
        # estimator's loss closures discard the carried state, so none is
        # produced here.
        y = dispatch.mamba2_mix(xh * dt[..., None], bmat, cmat, decay)
        state = None
    else:
        if state is None:
            state = jnp.zeros((B, H, hd, N), jnp.float32)

        def step(h, xs):
            xt, bt, ct, dct, dtt = xs    # (B,H,hd), (B,N), (B,N), (B,H), (B,H)
            upd = jnp.einsum("bhi,bn->bhin", xt * dtt[..., None], bt)
            h = dct[..., None, None] * h + upd
            yt = jnp.einsum("bhin,bn->bhi", h, ct)
            return h, yt

        xs = (xh.transpose(1, 0, 2, 3), bmat.transpose(1, 0, 2),
              cmat.transpose(1, 0, 2), decay.transpose(1, 0, 2),
              dt.transpose(1, 0, 2))
        state, ys = jax.lax.scan(step, state, xs)
        y = ys.transpose(1, 0, 2, 3)                           # (B,S,H,hd)
    out = mamba2_finish(cfg, p, y, z, xh, x.dtype, peft_layer, lora_scale)
    return out, state, conv_state
