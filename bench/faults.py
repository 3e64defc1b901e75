"""Faults of the timed path, for the tests that see ``correct`` come out
false and for the readings that set the limits (``bench/readings.py``):

  unchanged   the step returns its state unchanged
  half_batch  half of the batch left out, the mean taken over the rest:
              each client's second half of rows replaced by its first half
              (with one row per client, the second half of the clients by
              the first half)
  altered     the answer altered where it is produced: the largest unit's
              change in the new trainable tree doubled
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def half_batch(tokens, labels):
    """Works on numpy or jax arrays of shape (M, B, T) / (M, B)."""
    M, B = labels.shape
    if B >= 2:
        h = B // 2
        keep = list(range(h)) + list(range(B - h))
        return tokens[:, keep], labels[:, keep]
    h = M // 2
    keep = list(range(h)) + list(range(M - h))
    return tokens[keep], labels[keep]


def unchanged(step):
    def wrapped(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return wrapped


def half(step):
    def wrapped(state, batch):
        tokens, labels = half_batch(batch["tokens"], batch["labels"])
        return step(state, {"tokens": tokens, "labels": labels})
    return wrapped


def double_largest_unit(old, new):
    """``new`` with the change of its largest unit (one layer's slice of a
    stacked factor under 'layers', or a head leaf) doubled; traceable."""
    paths, olds = zip(*jax.tree_util.tree_leaves_with_path(old))
    news = jax.tree.leaves(new)
    stacked = [any(getattr(k, "key", None) == "layers" for k in p)
               for p in paths]
    norms = []
    for a, b, s in zip(olds, news, stacked):
        d = (b - a).reshape((b.shape[0], -1) if s else (1, -1))
        norms.append(jnp.linalg.norm(d, axis=1))
    flat = jnp.concatenate(norms)
    pick = jnp.argmax(flat)
    out, start = [], 0
    for a, b, s, n in zip(olds, news, stacked, norms):
        hit = (jnp.arange(n.shape[0]) + start) == pick
        start += n.shape[0]
        hit = hit.reshape((-1,) + (1,) * (b.ndim - 1)) if s else hit[0]
        out.append(b + jnp.where(hit, b - a, 0.0))
    return jax.tree.unflatten(jax.tree.structure(new), out)


def altered(step):
    def wrapped(state, batch):
        new, metrics = step(state, batch)
        return new._replace(peft=double_largest_unit(state.peft, new.peft)), \
            metrics
    return wrapped
