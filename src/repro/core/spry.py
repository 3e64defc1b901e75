"""SPRY round step (paper Alg. 1) as a single jittable function.

One call = one FL round:
  1. cyclic unit->client assignment masks (assignment.py)
  2. per-client seeded perturbations + forward-gradient local training,
     vmapped over the M simulated clients (client m sees only its own
     minibatch slice and perturbs only its assigned units)
  3. weighted-union aggregation of the per-unit deltas (clients that share a
     unit are averaged, FedAvg-style)
  4. adaptive server update (FedYogi default) on the effective gradient

The same function lowers for the production mesh: the client axis (M) and
per-client batch are sharded over ('pod','data'); base weights are
tensor/2D-sharded over ('model' [, 'data']). See launch/.

The round is decomposed into named pieces so the federation runtime
(fl/runtime/) can execute the SAME math through explicit messages and a
device-parallel cohort executor instead of one in-process vmap:

  make_client_update_fn   per-epoch client: local forward-gradient SGD,
                          returns the masked delta (the wire payload)
  make_client_jvp_fn      per-iteration client: one estimate, returns the
                          K jvp scalars (the wire payload)
  make_rebuild_fn         per-iteration server side: regenerate the
                          perturbations from the seed chain and rebuild the
                          client's gradient from its jvp scalars
  make_count_tree         per-unit client-count divisor tree (head counted
                          by every participating client)
  aggregate_payloads      weighted-union average of stacked client payloads

``make_round_step`` / ``make_round_step_per_iteration`` compose exactly
these pieces; the runtime's ideal path (full participation, no wire
quantization, whole-cohort executor) is bit-identical by construction —
asserted in tests/test_runtime.py.

Each piece runs under a ``jax.named_scope`` that names its phase in the
compiled program's HLO metadata: ``spry.client_update`` (the client fns:
mask trees, keys, perturbations, the jvp·v combine, the local SGD step and
the delta), ``spry.server`` (the round steps' own work — assignment, round
key, metrics — and the rebuild, aggregation and server optimizer). The
estimator inside a client fn names its own ``spry.primal`` and
``spry.tangents`` (core/forward_grad.py). Scopes nest: the last ``spry.``
component of an op's ``op_name`` names its phase, save that an op under
``spry.tangents`` is always tangent work (bench/phases.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.assignment import (
    assignment_matrix,
    build_mask_tree,
    client_counts,
    enumerate_units,
)
from repro.core.forward_grad import forward_gradient, reconstruct_gradient
from repro.fl.server import ServerState, server_init, server_update
from repro.models.registry import get_loss_fn
from repro.utils.pytree import tree_cast


class SpryState(NamedTuple):
    base: Any
    peft: Any
    server: ServerState
    round_idx: jnp.ndarray


def init_state(base, peft) -> SpryState:
    peft32 = tree_cast(peft, jnp.float32)
    return SpryState(base, peft32, server_init(peft32), jnp.zeros([], jnp.int32))


# ---------------------------------------------------------------------------
# Client-side pieces
# ---------------------------------------------------------------------------

def make_task_loss(cfg, spry_cfg, task, base, batch):
    """The client objective as a function of the peft tree only. With
    ``spry_cfg.fused_contraction`` the registry's SplitLoss builder is used
    (the final mixer site is declared, so the estimator runs the in-kernel
    jvp-contraction epilogues); otherwise the plain closure. Both trace the
    identical loss program — the split is a capability, not a numerics
    change."""
    if spry_cfg.fused_contraction:
        return get_loss_fn(task, split=True)(
            cfg, base, batch, lora_scale=spry_cfg.lora_alpha)
    loss_fn_kind = get_loss_fn(task)

    def loss_of(p):
        return loss_fn_kind(cfg, base, p, batch,
                            lora_scale=spry_cfg.lora_alpha)
    return loss_of


def estimator_route(spry_cfg) -> str:
    """The gradient-estimator route the client fns take ('fused' = in-kernel
    jvp-contraction at the final mixer site; 'standard' = materialize tangent
    outputs then contract). Surfaced in round metrics / train-loop logs."""
    return "fused" if spry_cfg.fused_contraction else "standard"


def run_fields(spry_cfg) -> dict:
    """Static estimator facts stamped on run artifacts (telemetry
    ``run_meta`` events, report headers): the active route plus the knobs
    that select it."""
    return {
        "route": estimator_route(spry_cfg),
        "k_perturbations": int(spry_cfg.k_perturbations),
        "tangent_batch": (int(spry_cfg.tangent_batch)
                          if spry_cfg.tangent_batch is not None else None),
        "local_iters": int(spry_cfg.local_iters),
        "local_lr": float(spry_cfg.local_lr),
        "server_lr": float(spry_cfg.server_lr),
    }


def make_client_update_fn(cfg, spry_cfg, task: str = "cls"):
    """Per-epoch client computation (paper Alg. 1 lines 6-13).

    Returns ``client_update(base, peft, round_key, seed_id, mask_row,
    client_batch) -> (delta, loss_mean, jvps)``:
    ``spry_cfg.local_iters`` steps of forward-gradient SGD on the units
    selected by ``mask_row``, starting from the server ``peft``. ``seed_id``
    is the client's position in the round (the fold_in chain the server
    shares), ``delta`` the masked weight change — the per-epoch wire payload.
    """
    K = spry_cfg.k_perturbations
    lr_l = spry_cfg.local_lr

    @jax.named_scope("spry.client_update")
    def client_update(base, peft, round_key, seed_id, mask_row, client_batch):
        index = enumerate_units(peft)
        mask_tree = build_mask_tree(peft, index, mask_row)
        ckey = jax.random.fold_in(round_key, seed_id)
        mb = spry_cfg.microbatch_size

        def grad_of(peft_c, ikey):
            if mb is None or mb >= client_batch["tokens"].shape[0]:
                loss_of = make_task_loss(cfg, spry_cfg, task, base,
                                         client_batch)
                return forward_gradient(
                    loss_of, peft_c, ikey, k_perturbations=K,
                    mask_tree=mask_tree, jvp_clip=spry_cfg.jvp_clip,
                    tangent_batch=spry_cfg.tangent_batch,
                    fused_contraction=spry_cfg.fused_contraction)
            # gradient accumulation: scan over microbatches, fresh
            # perturbation per microbatch (each estimate is unbiased for
            # its microbatch gradient; the average is unbiased for the
            # full-batch gradient), bounded activation memory
            B = client_batch["tokens"].shape[0]
            n_mb = B // mb
            mb_batch = jax.tree.map(
                lambda x: x[: n_mb * mb].reshape((n_mb, mb) + x.shape[1:]),
                client_batch)

            def mb_step(acc, xs):
                i, one = xs
                loss_of = make_task_loss(cfg, spry_cfg, task, base, one)
                loss, g, jvps = forward_gradient(
                    loss_of, peft_c, jax.random.fold_in(ikey, i),
                    k_perturbations=K, mask_tree=mask_tree,
                    jvp_clip=spry_cfg.jvp_clip,
                    tangent_batch=spry_cfg.tangent_batch,
                    fused_contraction=spry_cfg.fused_contraction)
                g_acc, loss_acc = acc
                g_acc = jax.tree.map(lambda a, b: a + b / n_mb, g_acc, g)
                return (g_acc, loss_acc + loss / n_mb), jvps

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              peft_c)
            (g, loss), jvps = jax.lax.scan(
                mb_step, (g0, jnp.float32(0.0)),
                (jnp.arange(n_mb), mb_batch))
            return loss, g, jvps.reshape(-1)[:K]

        def local_iter(carry, it):
            peft_c = carry
            ikey = jax.random.fold_in(ckey, it)
            loss, g, jvps = grad_of(peft_c, ikey)
            # local SGD on assigned units only (mask already zeroes g
            # outside the assignment, incl. the always-on head)
            peft_c = jax.tree.map(lambda p, gi: p - lr_l * gi, peft_c, g)
            return peft_c, (loss, jvps)

        peft_c, (losses, jvps) = jax.lax.scan(
            local_iter, peft, jnp.arange(spry_cfg.local_iters))
        delta = jax.tree.map(lambda a, b: a - b, peft_c, peft)
        return delta, losses.mean(), jvps

    return client_update


def make_client_jvp_fn(cfg, spry_cfg, task: str = "cls"):
    """Per-iteration client computation (paper §3.2): one forward-jvp at the
    current server weights; the K jvp scalars are the entire uplink payload.

    Returns ``client_jvp(base, peft, round_key, seed_id, mask_row,
    client_batch) -> (loss, jvps)``.
    """
    K = spry_cfg.k_perturbations

    @jax.named_scope("spry.client_update")
    def client_jvp(base, peft, round_key, seed_id, mask_row, client_batch):
        index = enumerate_units(peft)
        mask_tree = build_mask_tree(peft, index, mask_row)
        ckey = jax.random.fold_in(round_key, seed_id)
        ikey = jax.random.fold_in(ckey, 0)
        loss_of = make_task_loss(cfg, spry_cfg, task, base, client_batch)

        loss, _, jvps = forward_gradient(
            loss_of, peft, ikey, k_perturbations=K, mask_tree=mask_tree,
            jvp_clip=spry_cfg.jvp_clip,
            tangent_batch=spry_cfg.tangent_batch,
            fused_contraction=spry_cfg.fused_contraction)
        return loss, jvps

    return client_jvp


def make_rebuild_fn():
    """Server-side per-iteration gradient rebuild: regenerate v from the seed
    chain and combine with the client's jvp scalars (bit-identical to the
    client estimate, see forward_grad.reconstruct_gradient).

    Returns ``rebuild(peft, round_key, seed_id, mask_row, jvps) -> grad``.
    """
    @jax.named_scope("spry.server")
    def rebuild(peft, round_key, seed_id, mask_row, jvps):
        index = enumerate_units(peft)
        mask_tree = build_mask_tree(peft, index, mask_row)
        ckey = jax.random.fold_in(round_key, seed_id)
        ikey = jax.random.fold_in(ckey, 0)
        return reconstruct_gradient(peft, ikey, jvps, mask_tree)

    return rebuild


# ---------------------------------------------------------------------------
# Aggregation pieces
# ---------------------------------------------------------------------------

def make_count_tree(peft, index, counts, head_count):
    """Per-unit divisor tree: M-tilde per LoRA unit (``counts``, shape (U,)),
    the participating-client count for the always-on head."""
    count_tree = build_mask_tree(peft, index, counts)
    return {
        g: (jax.tree.map(lambda x: jnp.full_like(x, head_count), count_tree[g])
            if g == "head" else count_tree[g])
        for g in count_tree
    }


@jax.named_scope("spry.server")
def aggregate_payloads(peft, index, stacked, counts, head_count):
    """Weighted-union average of stacked per-client payload trees.

    ``stacked`` leaves carry a leading client axis; clients that share a unit
    are averaged FedAvg-style (sum over clients / per-unit count).
    """
    count_tree = make_count_tree(peft, index, counts, head_count)
    return jax.tree.map(lambda leaf, c: leaf.sum(0) / c, stacked, count_tree)


# ---------------------------------------------------------------------------
# In-process round steps (one vmap over the M simulated clients)
# ---------------------------------------------------------------------------

def make_round_step(cfg, spry_cfg, task: str = "cls", split: bool = True):
    """Build the jittable round_step(state, batch) -> (state, metrics).

    batch: {"tokens": (M, B, S), ...} — leading axis = simulated clients.
    split=False disables the paper's weight splitting (the FedFGD ablation:
    every client perturbs ALL trainable units).
    """
    M = spry_cfg.n_clients_per_round
    client_update = make_client_update_fn(cfg, spry_cfg, task)

    @jax.named_scope("spry.server")
    def round_step(state: SpryState, batch):
        base, peft = state.base, state.peft
        index = enumerate_units(peft)
        if split:
            mask_matrix = assignment_matrix(index.n_units, M,
                                            state.round_idx % M)
        else:
            mask_matrix = jnp.ones((M, index.n_units), jnp.float32)
        counts = client_counts(mask_matrix)                      # (U,)
        round_key = jax.random.fold_in(
            jax.random.PRNGKey(spry_cfg.seed), state.round_idx)

        deltas, losses, jvps = jax.vmap(
            lambda sid, row, cb: client_update(base, peft, round_key, sid,
                                               row, cb))(
            jnp.arange(M), mask_matrix, batch)

        # --- weighted union over clients (paper: FedAvg-style average over
        # the clients assigned to each unit; head trained by all M) ---
        delta = aggregate_payloads(peft, index, deltas, counts, M)

        new_peft, server = server_update(
            spry_cfg.server_opt, peft, delta, state.server,
            lr=spry_cfg.server_lr)
        metrics = {
            "loss": losses.mean(),
            "jvp_abs_mean": jnp.abs(jvps).mean(),
            "delta_norm": jnp.sqrt(sum(jnp.sum(d * d) for d in jax.tree.leaves(delta))),
            # active estimator route (1.0 = fused jvp-contraction epilogues
            # at the final mixer site, 0.0 = standard materializing route)
            "fused_route": jnp.float32(spry_cfg.fused_contraction),
        }
        return SpryState(base, new_peft, server, state.round_idx + 1), metrics

    return round_step


# ---------------------------------------------------------------------------
# Per-iteration communication mode (paper §3.2): clients send back only the
# jvp scalar; the server regenerates the perturbations from the shared seed
# and applies the global update directly.
# ---------------------------------------------------------------------------

def make_round_step_per_iteration(cfg, spry_cfg, task: str = "cls"):
    M = spry_cfg.n_clients_per_round
    client_jvp = make_client_jvp_fn(cfg, spry_cfg, task)
    rebuild = make_rebuild_fn()

    @jax.named_scope("spry.server")
    def round_step(state: SpryState, batch):
        base, peft = state.base, state.peft
        index = enumerate_units(peft)
        mask_matrix = assignment_matrix(index.n_units, M, state.round_idx % M)
        counts = client_counts(mask_matrix)
        round_key = jax.random.fold_in(
            jax.random.PRNGKey(spry_cfg.seed), state.round_idx)

        # --- client side: one forward-jvp, transmit K scalars ---
        losses, jvps = jax.vmap(
            lambda sid, row, cb: client_jvp(base, peft, round_key, sid, row,
                                            cb))(
            jnp.arange(M), mask_matrix, batch)        # (M,), (M,K)

        # --- server side: regenerate v from the seed, rebuild gradients
        # (stacked-perturbation path, bit-identical to the client estimator
        # and O(1) trace size in K) ---
        grads = jax.vmap(
            lambda sid, row, jv: rebuild(peft, round_key, sid, row, jv))(
            jnp.arange(M), mask_matrix, jvps)
        grad = aggregate_payloads(peft, index, grads, counts, M)
        # server applies the *gradient direction* with its adaptive optimizer
        delta = jax.tree.map(lambda g: -spry_cfg.local_lr * g, grad)
        new_peft, server = server_update(
            spry_cfg.server_opt, peft, delta, state.server,
            lr=spry_cfg.server_lr)
        metrics = {"loss": losses.mean(), "jvp_abs_mean": jnp.abs(jvps).mean(),
                   "fused_route": jnp.float32(spry_cfg.fused_contraction)}
        return SpryState(base, new_peft, server, state.round_idx + 1), metrics

    return round_step
