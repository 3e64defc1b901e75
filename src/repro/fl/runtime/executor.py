"""Cohort executors: run a per-client function over a round's cohort.

A *client kernel* is any ``fn(base, peft, round_key, seed_id, mask_row,
batch) -> (payload_tree, aux)`` where ``payload_tree`` is a peft-shaped tree
(the per-epoch delta, or the server-side rebuilt gradient in per-iteration
mode; may be an empty tuple for the jvp-only client pass) and ``aux`` is a
small per-client pytree (loss, jvp scalars) that is always stacked.

Two execution strategies, both traceable inside the engine's single jit:

  SerialExecutor    single device. microbatch=None runs ONE vmap over the
                    whole cohort and returns stacked payloads — this is
                    op-identical to the in-process round step (vmap widths
                    change CPU numerics at the ~1e-7 level, so bit-identity
                    REQUIRES the same width; asserted in tests). A finite
                    microbatch m instead lax.scans over C/m chunks and
                    stream-accumulates Σ keep_i·payload_i, so peak
                    aggregation memory is O(m·|peft|) + O(|peft|)
                    independent of cohort size.
  ShardedExecutor   shard_map over the host's devices: each device scans its
                    C/D clients with the same chunked vmap and psums the
                    partial payload sums — server-side memory O(|peft|) per
                    device + one O(|peft|) replicated result, enabling
                    cohorts ≫ the in-process M. Per-client payloads (collect
                    mode) are bitwise-equal to the SerialExecutor at the
                    same microbatch (same per-chunk program, different
                    scheduling); only the cross-device reduction order
                    differs, so aggregates match to float tolerance.

``collect=True`` additionally materializes the (C, |peft|) payload stack —
used for wire simulation (pack real ClientUpdate messages) and equivalence
tests; the streaming mode is the scalable path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _weighted(tree, w):
    """Scale each client's payload leaf by its keep weight (leading C axis)."""
    return jax.tree.map(
        lambda x: x * w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype),
        tree)


def _chunk_run(client_fn, base, peft, round_key, seed_ids, mask_rows,
               batches, keep, microbatch, collect):
    """Shared chunked-vmap driver (single-device view of the cohort).

    Returns (payload, aux): payload stacked (C, ...) when collect else the
    keep-weighted streaming sum; aux always stacked (C, ...).
    """
    C = seed_ids.shape[0]
    vfn = jax.vmap(
        lambda sid, row, cb: client_fn(base, peft, round_key, sid, row, cb))

    if microbatch is None or microbatch >= C:
        payload, aux = vfn(seed_ids, mask_rows, batches)
        if not collect:
            payload = jax.tree.map(lambda x: x.sum(0),
                                   _weighted(payload, keep))
        return payload, aux

    m = int(microbatch)
    if C % m != 0:
        raise ValueError(f"cohort size {C} not divisible by microbatch {m} "
                         "(pad the cohort with keep=0 rows)")
    n = C // m
    xs = jax.tree.map(lambda x: x.reshape((n, m) + x.shape[1:]),
                      (seed_ids, mask_rows, batches, keep))

    def body(carry, chunk):
        sid, row, cb, kp = chunk
        payload, aux = vfn(sid, row, cb)
        if collect:
            return carry, (payload, aux)
        carry = jax.tree.map(
            jnp.add, carry, jax.tree.map(lambda x: x.sum(0),
                                         _weighted(payload, kp)))
        return carry, aux

    if collect:
        _, (payload, aux) = jax.lax.scan(body, (), xs)
        return (jax.tree.map(lambda x: x.reshape((C,) + x.shape[2:]), payload),
                jax.tree.map(lambda x: x.reshape((C,) + x.shape[2:]), aux))

    zeros = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32),
        jax.eval_shape(lambda: client_fn(base, peft, round_key, seed_ids[0],
                                         mask_rows[0],
                                         jax.tree.map(lambda b: b[0],
                                                      batches))[0]))
    payload_sum, aux = jax.lax.scan(body, zeros, xs)
    return payload_sum, jax.tree.map(
        lambda x: x.reshape((C,) + x.shape[2:]), aux)


class SerialExecutor:
    """Single-device cohort execution (reference / memory-bounded)."""

    def __init__(self, microbatch: Optional[int] = None):
        self.microbatch = microbatch

    @property
    def n_devices(self) -> int:
        return 1

    def pad_to(self, C: int) -> int:
        m = self.microbatch
        if m is None:
            return C
        return C + (-C) % m

    def run(self, client_fn, base, peft, round_key, seed_ids, mask_rows,
            batches, keep, *, collect: bool = False):
        return _chunk_run(client_fn, base, peft, round_key, seed_ids,
                          mask_rows, batches, keep, self.microbatch, collect)


class ShardedExecutor:
    """shard_map cohort execution over the host's devices.

    The cohort axis is split across ``devices``; each device runs the same
    chunked vmap as SerialExecutor on its shard. Streaming payload sums are
    psum-reduced (replicated O(|peft|) result); collect mode returns the
    cohort-stacked payloads (device-sharded in memory, gathered on exit).
    """

    def __init__(self, devices=None, microbatch: Optional[int] = None,
                 axis: str = "clients"):
        devices = jax.devices() if devices is None else list(devices)
        self.mesh = Mesh(np.array(devices), (axis,))
        self.axis = axis
        self.microbatch = microbatch

    @property
    def n_devices(self) -> int:
        return self.mesh.shape[self.axis]

    def pad_to(self, C: int) -> int:
        quantum = self.n_devices * (self.microbatch or 1)
        padded = C + (-C) % quantum
        if self.microbatch is None:
            padded = C + (-C) % self.n_devices
        return padded

    def run(self, client_fn, base, peft, round_key, seed_ids, mask_rows,
            batches, keep, *, collect: bool = False):
        C = seed_ids.shape[0]
        D = self.n_devices
        if C % D != 0:
            raise ValueError(f"cohort size {C} not divisible by {D} devices "
                             "(pad the cohort with keep=0 rows)")

        def local(base_l, peft_l, round_key_l, sid, row, cb, kp):
            return _chunk_run(client_fn, base_l, peft_l, round_key_l, sid,
                              row, cb, kp, self.microbatch, collect)

        payload_spec = P(self.axis) if collect else P()
        out = jax.shard_map(
            (lambda b, p, rk, sid, row, cb, kp:
             ((lambda pl, aux:
               (pl if collect else jax.lax.psum(pl, self.axis), aux))
              (*local(b, p, rk, sid, row, cb, kp)))),
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(self.axis), P(self.axis),
                      P(self.axis), P(self.axis)),
            out_specs=(payload_spec, P(self.axis)),
            check_vma=False,
        )(base, peft, round_key, seed_ids, mask_rows, batches, keep)
        return out


def pad_cohort(executor, seed_ids, mask_rows, batches, keep):
    """Pad cohort arrays to the executor's quantum with keep=0 rows (the pad
    rows still compute on garbage inputs but carry zero aggregation weight
    and are sliced off per-client outputs)."""
    C = len(seed_ids)
    Cp = executor.pad_to(C)
    if Cp == C:
        return seed_ids, mask_rows, batches, keep, C
    pad = Cp - C

    def padrow(x):
        x = np.asarray(x)
        return np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)

    return (padrow(seed_ids), padrow(mask_rows),
            jax.tree.map(padrow, batches),
            np.concatenate([np.asarray(keep), np.zeros(pad, keep.dtype)]), C)
