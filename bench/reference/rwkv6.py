"""Plain float32 reference of the rwkv6 family, as the program defines it.

It imports nothing of the program. ``init_base``/``init_peft`` make random
weights from one key in the program's tree layout and dtypes, so the same
arrays can be handed to the program's round step and, after a fresh
regeneration from the same key, to this reference. ``loss`` is the
classification objective in straightforward ``jax.numpy``:

    h_0 = embed[tokens]
    per layer:  h += TimeMix(rms(h)) ;  h += ChannelMix(rms(h))
    logits = rms(h)[:, -1] @ head.w + head.b ;  loss = mean CE

    TimeMix:  xs = shift(x); x_i = x + (xs - x) * mu_i  (i = r, k, v, g, w)
              r, k, v, g = x_r Wr (+ LoRA), x_k Wk, x_v Wv (+ LoRA), x_g Wg
              w = exp(-exp(w0 + (x_w A_w) B_w))
              S_t = diag(w_t) S_{t-1} + k_t v_t^T
              y_t = r_t^T (S_{t-1} + (u * k_t) v_t^T)          (per head)
              out = (GroupNorm(y) * ln_w + ln_b) * silu(g) @ Wo
    ChannelMix: xs = shift(x); out = sigmoid(x Wcr) * (relu(xs Wck)^2 Wcv)

Departures from the published RWKV6 (arXiv:2404.05892) that the program
makes, and this reference follows: a static token-shift mix ``mu`` (no
data-dependent lerp), RMSNorm in place of LayerNorm around the blocks, a
classification head on the last position instead of the LM head, and LoRA
rank-1 adapters on Wr and Wv.

Every matrix product goes through ``mm``, which the control replaces with
one whose operands and result are rounded to float8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DECAY_RANK = 64
F32 = jnp.float32


def _sizes(cfg):
    d = cfg["d_model"]
    hd = cfg["ssm"]["head_dim"]
    return cfg["n_layers"], d, d // hd, hd, cfg["d_ff"], cfg["vocab"]


def init_base(cfg, key):
    """Frozen base in the program's layout: LeCun-normal projections in the
    configuration's dtype, norms at one, decay bias 0.5, token-shift mixes
    uniform in [0, 1)."""
    L, d, H, hd, F, V = _sizes(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, dtype=dt, scale=1.0):
        x = jax.random.normal(next(keys), shape, F32)
        return (x * (scale / np.sqrt(fan_in))).astype(dtype)

    ones = lambda *s: jnp.ones(s, F32)
    mix = {
        "wr": dense((L, d, d), d), "wk": dense((L, d, d), d),
        "wv": dense((L, d, d), d), "wg": dense((L, d, d), d),
        "wo": dense((L, d, d), d),
        "w_lora_a": dense((L, d, DECAY_RANK), d),
        "w_lora_b": dense((L, DECAY_RANK, d), DECAY_RANK, scale=0.1),
        "w0": jnp.full((L, d), 0.5, F32),
        "mu": jax.random.uniform(next(keys), (L, 5, d), F32),
        "u": dense((L, H, hd), H, dtype=F32),
        "ln_w": ones(L, d), "ln_b": jnp.zeros((L, d), F32),
        "cm_wr": dense((L, d, d), d), "cm_wk": dense((L, d, F), d),
        "cm_wv": dense((L, F, d), F),
    }
    return {
        "embed": dense((V, d), d),
        "layers": {"mix": mix, "ln1": {"w": ones(L, d)},
                   "ln2": {"w": ones(L, d)}},
        "final_norm": {"w": ones(d)},
        "lm_head": dense((d, V), d),
    }


def init_peft(cfg, key):
    """Trainable tree: a LoRA pair (A LeCun-normal, B zero) per target and
    layer, and the classifier head, all float32."""
    L, d = cfg["n_layers"], cfg["d_model"]
    r, C = cfg["lora_rank"], cfg["n_classes"]
    keys = jax.random.split(key, len(cfg["lora_targets"]) + 1)
    layers = {t: {"A": jax.random.normal(k, (L, d, r), F32) / np.sqrt(d),
                  "B": jnp.zeros((L, r, d), F32)}
              for t, k in zip(cfg["lora_targets"], keys[1:])}
    head = {"w": jax.random.normal(keys[0], (d, C), F32) / np.sqrt(d),
            "b": jnp.zeros((C,), F32)}
    return {"layers": layers, "head": head}


def plain_mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision="highest")


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """Sequential WKV6 from a zero state; r, k, v, w: (B, S, H, hd)."""
    def step(s, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        yt = jnp.einsum("bhi,bhij->bhj", rt, s + u[None, :, :, None] * kv,
                        precision="highest")
        return wt[..., None] * s + kv, yt

    B, S, H, hd = r.shape
    xs = [t.transpose(1, 0, 2, 3) for t in (r, k, v, w)]
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, hd, hd), F32), xs)
    return ys.transpose(1, 0, 2, 3)


def _layer(cfg, mm, scale, h, lp, pl):
    L, d, H, hd, F, V = _sizes(cfg)
    p = jax.tree.map(lambda t: t.astype(F32), lp["mix"])
    B, S, _ = h.shape

    def lora(x, w, name):
        y = mm(x, w)
        if name in pl:
            y = y + mm(mm(x, pl[name]["A"]), pl[name]["B"]) * scale
        return y

    x = _rms(h, lp["ln1"]["w"])
    xs = _shift(x)
    lerp = [x + (xs - x) * p["mu"][i] for i in range(5)]
    r = lora(lerp[0], p["wr"], "wr")
    k = lora(lerp[1], p["wk"], "wk")
    v = lora(lerp[2], p["wv"], "wv")
    g = lora(lerp[3], p["wg"], "wg")
    w = jnp.exp(-jnp.exp(p["w0"] + mm(mm(lerp[4], p["w_lora_a"]),
                                      p["w_lora_b"])))
    heads = lambda t: t.reshape(B, S, H, hd)
    y = _wkv(heads(r), heads(k), heads(v), heads(w), p["u"])
    y = (y - y.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        y.var(-1, keepdims=True) + 1e-5)
    y = y.reshape(B, S, d) * p["ln_w"] + p["ln_b"]
    h = h + lora(y * jax.nn.silu(g), p["wo"], "wo")

    x = _rms(h, lp["ln2"]["w"])
    xs = _shift(x)
    kk = jnp.square(jax.nn.relu(mm(xs, p["cm_wk"])))
    return h + jax.nn.sigmoid(mm(x, p["cm_wr"])) * mm(kk, p["cm_wv"])


def loss(cfg, base, peft, batch, mm=plain_mm):
    """Mean classification cross-entropy of one client's batch."""
    scale = cfg["lora_alpha"]
    h = jnp.take(base["embed"], batch["tokens"], axis=0).astype(F32)

    def body(h, xs):
        lp, pl = xs
        return _layer(cfg, mm, scale, h, lp, pl), None

    h, _ = jax.lax.scan(body, h, (base["layers"], peft["layers"]))
    h = _rms(h, base["final_norm"]["w"])
    logits = mm(h[:, -1], peft["head"]["w"]) + peft["head"]["b"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], -1).mean()
