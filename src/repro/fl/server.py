"""Server-side federated optimizers (paper §3.1, Reddi et al. AFO).

The server treats the aggregated client delta  Δ = w' − w  as a pseudo-
gradient and applies FedAvg / FedSGD / FedAdam / FedYogi / FedAdagrad.
All are pure pytree functions so they compose into the jitted round step,
where ``server_update`` names its work ``spry.server`` (the round step's
server phase, core/spry.py).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils.pytree import tree_zeros_like


class ServerState(NamedTuple):
    count: jnp.ndarray
    m: Any            # first moment of deltas
    v: Any            # second moment of deltas


def server_init(params) -> ServerState:
    return ServerState(jnp.zeros([], jnp.int32), tree_zeros_like(params),
                       tree_zeros_like(params))


@jax.named_scope("spry.server")
def server_update(kind: str, params, delta, state: ServerState, lr: float,
                  b1: float = 0.9, b2: float = 0.99, tau: float = 1e-3):
    """Apply one server-optimizer step. ``delta`` is the aggregated client
    update direction (already weighted-averaged over clients per layer).

    Returns (new_params, new_state).
    """
    count = state.count + 1
    if kind in ("fedavg", "fedsgd"):
        # FedAvg: w <- w + Δ (server lr folded to 1.0 for parity with paper);
        # FedSGD is the same rule applied every iteration.
        new_params = jax.tree.map(lambda p, d: (p + lr * d).astype(p.dtype),
                                  params, delta)
        return new_params, ServerState(count, state.m, state.v)

    m = jax.tree.map(lambda mi, d: b1 * mi + (1 - b1) * d, state.m, delta)

    if kind == "fedadam":
        v = jax.tree.map(lambda vi, d: b2 * vi + (1 - b2) * d * d, state.v, delta)
    elif kind == "fedyogi":
        v = jax.tree.map(
            lambda vi, d: vi - (1 - b2) * jnp.sign(vi - d * d) * (d * d),
            state.v, delta)
    elif kind == "fedadagrad":
        v = jax.tree.map(lambda vi, d: vi + d * d, state.v, delta)
    else:
        raise ValueError(f"unknown server optimizer {kind!r}")

    new_params = jax.tree.map(
        lambda p, mi, vi: (p + lr * mi / (jnp.sqrt(vi) + tau)).astype(p.dtype),
        params, m, v)
    return new_params, ServerState(count, m, v)
