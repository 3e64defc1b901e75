"""Decay regimes for the WKV6 kernel tests.

``sigmoid``: w in (0, 1), the tests' default. ``strong``: every fourth key
channel decays by down to 1e-30 a token, and every seventh token (from the
fourth) has w exactly 0 in every channel, which wipes the state. ``near1``:
w = 1 - 1e-6 everywhere, so the state keeps nearly all it ever saw.
"""
import jax
import jax.numpy as jnp

DECAYS = ("sigmoid", "strong", "near1")


def wkv6_decay(kind, key, shape):
    """w of ``shape`` (..., S, H, hd) for the regime ``kind``."""
    k1, k2 = jax.random.split(key)
    w = jax.nn.sigmoid(jax.random.normal(k1, shape))
    if kind == "sigmoid":
        return w
    if kind == "near1":
        return jnp.full(shape, 1.0 - 1e-6, jnp.float32)
    assert kind == "strong", kind
    channel = jnp.arange(shape[-1]) % 4 == 0
    tiny = 10.0 ** (-30.0 * jax.random.uniform(k2, shape))
    w = jnp.where(channel, tiny, w)
    token = (jnp.arange(shape[-3]) % 7 == 3)[:, None, None]
    return jnp.where(token, 0.0, w)
