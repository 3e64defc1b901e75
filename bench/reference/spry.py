"""Plain reference of one Spry round (paper Alg. 1) with the FedYogi server
step, written from the algorithm and independent of the program.

Units are the per-layer LoRA pairs of each target (groups and targets in
sorted order, layers in order); client ``(i + round % M) % M`` trains unit
``i % U`` for ``i < max(U, M)``; every client also trains the head. Each
client perturbs its units with ``v ~ N(0, I)`` drawn per leaf from the key
chain ``fold_in(fold_in(fold_in(fold_in(PRNGKey(seed), round), client),
iteration 0), tangent i)``, takes one SGD step on ``(1/K) sum_i jvp_i v_i``
and sends its change; the server averages each unit over the clients that
trained it and applies FedYogi (b1 0.9, b2 0.99, tau 1e-3) with zero
initial moments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

B1, B2, TAU = 0.9, 0.99, 1e-3


STACKED = ("layers", "enc_layers")


def unit_masks(peft, round_idx, n_clients):
    """Per client, a tree of 0/1 masks broadcastable against ``peft``, and
    the tree of per-unit client counts (the head: every client)."""
    units = []
    for group in sorted(g for g in peft if g != "head"):
        for target in sorted(peft[group]):
            if group in STACKED:
                n = jax.tree.leaves(peft[group][target])[0].shape[0]
                units += [(group, target, layer) for layer in range(n)]
            else:
                units.append((group, target, None))
    M, U = n_clients, len(units)
    owner = [[0.0] * U for _ in range(M)]
    for i in range(max(U, M)):
        owner[(i + round_idx) % M][i % U] = 1.0
    counts = [max(sum(owner[c][u] for c in range(M)), 1.0) for u in range(U)]

    def tree_of(values):
        out = {"head": jax.tree.map(lambda _: jnp.ones((), jnp.float32),
                                    peft["head"])}
        per_layer = {}
        for u, (group, target, layer) in enumerate(units):
            if layer is None:
                out.setdefault(group, {})[target] = jax.tree.map(
                    lambda _: jnp.float32(values[u]), peft[group][target])
            else:
                per_layer.setdefault((group, target), []).append(values[u])
        for (group, target), vals in per_layer.items():
            out.setdefault(group, {})[target] = jax.tree.map(
                lambda leaf: jnp.asarray(vals, jnp.float32).reshape(
                    (leaf.shape[0],) + (1,) * (leaf.ndim - 1)),
                peft[group][target])
        return out

    masks = [tree_of(owner[c]) for c in range(M)]
    count_tree = tree_of(counts)
    count_tree["head"] = jax.tree.map(
        lambda _: jnp.full((), float(M), jnp.float32), peft["head"])
    return masks, count_tree


def perturbation(key, peft, mask):
    leaves, treedef = jax.tree.flatten(peft)
    keys = jax.random.split(key, len(leaves))
    v = [jax.random.normal(k, x.shape, jnp.float32)
         for k, x in zip(keys, leaves)]
    return jax.tree.map(lambda a, m: a * m, jax.tree.unflatten(treedef, v),
                        mask)


def make_client(loss_fn, k_tangents, local_lr):
    """Jitted ``client(base, peft, key, mask, batch) -> (loss, change of the
    trainable tree)`` after one forward-gradient SGD step, where
    ``loss_fn(base, peft, batch)`` is the client objective."""
    @jax.jit
    def client(base, peft, key, mask, batch):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(k_tangents))
        vs = jax.vmap(lambda kk: perturbation(kk, peft, mask))(keys)
        loss_of = lambda p: loss_fn(base, p, batch)
        losses, jvps = jax.vmap(
            lambda v: jax.jvp(loss_of, (peft,), (v,)))(vs)
        grad = jax.tree.map(
            lambda x: jnp.tensordot(jvps, x, axes=1) / k_tangents, vs)
        new = jax.tree.map(lambda p, g: p - local_lr * g, peft, grad)
        return losses[0], jax.tree.map(jnp.subtract, new, peft)

    return client


def round_step(client, base, peft, server, round_idx, batch, *, seed,
               server_lr):
    """One round through ``client`` (``make_client``). ``batch`` leaves have
    a leading client axis; ``server`` is (m, v). Returns (mean client loss,
    aggregated change, new peft, (m, v))."""
    M = batch["labels"].shape[0]
    masks, count_tree = unit_masks(peft, round_idx, M)
    round_key = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    losses, total = [], None
    for c in range(M):
        one = jax.tree.map(lambda x: x[c], batch)
        key = jax.random.fold_in(jax.random.fold_in(round_key, c), 0)
        loss, change = client(base, peft, key, masks[c], one)
        losses.append(float(loss))
        total = change if total is None else jax.tree.map(jnp.add, total,
                                                          change)
    delta = jax.tree.map(jnp.divide, total, count_tree)
    m, v = server
    m = jax.tree.map(lambda mi, d: B1 * mi + (1 - B1) * d, m, delta)
    v = jax.tree.map(
        lambda vi, d: vi - (1 - B2) * jnp.sign(vi - d * d) * d * d, v, delta)
    new = jax.tree.map(
        lambda p, mi, vi: p + server_lr * mi / (jnp.sqrt(vi) + TAU),
        peft, m, v)
    return sum(losses) / M, delta, new, (m, v)
