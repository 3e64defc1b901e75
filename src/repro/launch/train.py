"""FL-simulation training driver (CPU-runnable; the multi-device path is
exercised by dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch roberta-large-lora \
        --task sst2 --method spry --rounds 200 --clients 8

Runs the full paper pipeline: synthetic task -> Dirichlet partition ->
client sampling -> jitted round step (SPRY or a baseline) -> server update,
with periodic generalized/personalized evaluation.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SpryConfig, get_config, reduce_config
from repro.core import (
    estimator_route,
    init_state,
    make_round_step,
    make_round_step_per_iteration,
    run_fields,
)
from repro.core.baselines import make_backprop_round_step, make_zeroorder_round_step
from repro.core.baselines.zeroorder import ZOState, init_zo_state

# round-state donation through the jitted step: CPU sometimes declines
# individual buffers — harmless, not worth a per-round warning
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")
from repro.data import make_task
from repro.data.loader import ClientDataset, stack_client_batches
from repro.fl import dirichlet_partition, sample_clients
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cls_logits, get_model
from repro.models.common import accuracy_from_logits
from repro.obs import NULL, MemoryProbe, compile_stats, make_telemetry
from repro.peft import init_peft


METHODS = ("spry", "spry_periter", "fedavg", "fedyogi", "fedsgd",
           "fedavgsplit", "fedfgd", "fedmezo", "baffle", "fwdllm")


def personalized_accuracy(cfg, state, clients, x, y, rng, steps=5,
                          lr=5e-2, batch_size=8, max_clients=8):
    """Paper's Acc_p: each client finetunes the trainable head on its own
    shard (the personalisation layers SPRY assigns to every client, §3.1)
    and is evaluated on its own held-out samples."""
    from repro.core.forward_grad import forward_gradient
    from repro.models.registry import cls_loss

    head_mask = {g: jax.tree.map(
        lambda leaf: jnp.float32(1.0 if g == "head" else 0.0), t)
        for g, t in state.peft.items()}

    # jitted once per shape with the base as an argument: run op by op, a
    # full-width model re-traces and recompiles its layer scan every step
    @jax.jit
    def head_step(base, peft, tokens, labels, key):
        # head-only forward-gradient step (stays in the paper's paradigm)
        batch = {"tokens": tokens, "labels": labels}
        _, g, _ = forward_gradient(lambda p: cls_loss(cfg, base, p, batch),
                                   peft, key, mask_tree=head_mask)
        return jax.tree.map(lambda p_, g_: p_ - lr * g_, peft, g)

    @jax.jit
    def held_out_acc(base, peft, tokens, labels):
        logits = cls_logits(cfg, base, peft, {"tokens": tokens})
        return accuracy_from_logits(logits, labels)

    accs = []
    for c in clients[:max_clients]:
        idx = c.indices
        if len(idx) < 4:
            continue
        cut = max(2, int(0.8 * len(idx)))
        tr, te = idx[:cut], idx[cut:]
        peft = state.peft
        for s in range(steps):
            take = rng.choice(tr, size=min(batch_size, len(tr)), replace=False)
            peft = head_step(state.base, peft, jnp.asarray(x[take]),
                             jnp.asarray(y[take]),
                             jax.random.PRNGKey(int(take[0]) + s))
        accs.append(float(held_out_acc(state.base, peft, jnp.asarray(x[te]),
                                       jnp.asarray(y[te]))))
    return float(np.mean(accs)) if accs else float("nan")


def build_round_step(cfg, sc: SpryConfig, method: str, task="cls"):
    if method == "spry":
        return make_round_step(cfg, sc, task), "spry"
    if method == "spry_periter":
        return make_round_step_per_iteration(cfg, sc, task), "spry"
    if method == "fedfgd":
        # forward gradients WITHOUT splitting: every client perturbs all units
        return make_round_step(cfg, sc, task, split=False), "spry"
    if method in ("fedavg", "fedyogi", "fedsgd"):
        return make_backprop_round_step(cfg, sc, task, method=method), "bp"
    if method == "fedavgsplit":
        return make_backprop_round_step(cfg, sc, task, method="fedavg",
                                        split=True), "bp"
    if method in ("fedmezo", "baffle", "fwdllm"):
        return make_zeroorder_round_step(cfg, sc, task, method=method), "zo"
    raise ValueError(method)


def task_config(arch, task, seed, reduced):
    """The model config with a classifier head sized to ``task``, and the
    task's (x_train, y_train, x_test, y_test) arrays made from ``seed``."""
    cfg = get_config(arch)
    if reduced:
        cfg = reduce_config(cfg)
    data = make_task(task, seed=seed, vocab=cfg.vocab)
    return dataclasses.replace(cfg, n_classes=int(data[1].max()) + 1), data


def spry_config(method, local_lr=None, server_lr=None, **fields):
    """``SpryConfig`` for ``method`` with its default client/server learning
    rates and server optimizer; ``fields`` are the remaining SpryConfig
    fields."""
    defaults = {
        "spry": (5e-3, 1e-2), "spry_periter": (5e-3, 1e-2),
        "fedfgd": (5e-3, 1e-2),
        "fedavg": (5e-2, 1.0), "fedyogi": (5e-2, 1e-2), "fedsgd": (5e-2, 1.0),
        "fedavgsplit": (5e-2, 1.0),
        "fedmezo": (5e-3, 1e-2), "baffle": (5e-3, 1e-2), "fwdllm": (5e-3, 1e-2),
    }
    d_lr, d_slr = defaults[method]
    return SpryConfig(
        local_lr=local_lr if local_lr is not None else d_lr,
        server_lr=server_lr if server_lr is not None else d_slr,
        server_opt="fedavg" if method in ("fedavg", "fedsgd", "fedavgsplit")
        else "fedyogi",
        **fields)


def run_training(arch="roberta-large-lora", task="sst2", method="spry",
                 rounds=100, clients_per_round=8, total_clients=32,
                 batch_size=8, local_iters=1, local_lr=None, server_lr=None,
                 dirichlet_alpha=0.1, seed=0, eval_every=10, reduced=True,
                 k_perturbations=1, jvp_clip=None, tangent_batch=None,
                 fused_contraction=False, log=print,
                 runtime=False, runtime_executor="serial",
                 runtime_microbatch=None, over_select=1.0, deadline=None,
                 dropout_rate=0.0, wire_dtype="fp32", wire_simulate=False,
                 telemetry=None, faults=None, quorum=None,
                 checkpoint_dir=None, checkpoint_every=1, resume=False,
                 async_mode=False, buffer_size=4, staleness_decay=0.5,
                 async_concurrency=None, max_staleness=None, profile_dir=None):
    """Run ``rounds`` federated rounds; returns the eval history.

    With ``telemetry`` on, the in-process path spans each round's
    ``train.prep`` (client draw, batch stacking, host-to-device copy),
    ``train.dispatch`` (the jitted step's call, which returns before the
    device finishes) and ``train.loss_read`` (the loss read, which waits for
    it), and its ``round`` event carries ``compiles`` where the round
    compiled. ``profile_dir`` records a profiler trace of the round loop
    there, with those spans on the host line beside the device operations.
    """
    tel = telemetry if telemetry is not None else NULL
    if async_mode:
        # the async engine IS a runtime path (population + wire frames)
        runtime = True
    # fault injection rides the simulated wire (frames must exist to be
    # corrupted), so --faults implies --wire-simulate on the runtime path
    # (the async engine always frames its uplink)
    from repro.fl.runtime.faults import FaultConfig
    if isinstance(faults, str):
        faults = FaultConfig.parse(faults, seed=seed)
    if faults is not None and not faults.any_faults:
        faults = None
    if faults is not None:
        if not runtime:
            raise ValueError("--faults requires --runtime (the chaotic wire "
                             "lives in the federation engine)")
        wire_simulate = True
    cfg, (x_tr, y_tr, x_te, y_te) = task_config(arch, task, seed, reduced)
    sc = spry_config(
        method, n_clients_per_round=clients_per_round,
        n_total_clients=total_clients, local_iters=local_iters,
        local_lr=local_lr, server_lr=server_lr,
        k_perturbations=k_perturbations, jvp_clip=jvp_clip,
        tangent_batch=tangent_batch, fused_contraction=fused_contraction,
        dirichlet_alpha=dirichlet_alpha, seed=seed)

    route = estimator_route(sc)
    if tel.enabled:
        tel.event("run_meta", workload="train", method=method, arch=arch,
                  task=task, rounds=rounds, clients_per_round=clients_per_round,
                  total_clients=total_clients, batch_size=batch_size,
                  runtime=runtime, seed=seed, **run_fields(sc))
    if method in ("spry", "spry_periter", "fedfgd"):
        # surface the active gradient-estimator route (satellite of the
        # split-forward refactor: --fused-contraction no longer falls back
        # silently — the registry split losses serve every family, and the
        # estimator warns if it still receives an unsplittable loss)
        log(f"[{method}] estimator route: {route}"
            + (" (in-kernel jvp-contraction at the final mixer site)"
               if route == "fused" else ""))

    rng = np.random.default_rng(seed)

    key = jax.random.PRNGKey(seed)
    model = get_model(cfg)
    base = model.init_base(cfg, key)
    peft = init_peft(cfg, key, sc)
    state = init_state(base, peft)

    engine = scheduler = None
    if runtime:
        # federation-runtime path: logical client population with lazy
        # Dirichlet shards + cohort scheduler + message-level round engine
        # (or, with --async, the event-driven FedBuff engine)
        from repro.core.assignment import enumerate_units
        from repro.fl.runtime import (
            AsyncConfig, AsyncFederationEngine, ClientPopulation,
            CohortScheduler, FederationEngine, SerialExecutor,
            ShardedExecutor, WireConfig)
        if method not in ("spry", "spry_periter"):
            raise ValueError(f"--runtime supports spry/spry_periter, "
                             f"not {method!r}")
        comm_mode = "per_epoch" if method == "spry" else "per_iteration"
        population = ClientPopulation(
            x_tr, y_tr, n_clients=total_clients, alpha=dirichlet_alpha,
            seed=seed)
        if async_mode:
            engine = AsyncFederationEngine(
                cfg, sc, population, task="cls", comm_mode=comm_mode,
                async_cfg=AsyncConfig(
                    buffer_size=buffer_size,
                    staleness_decay=staleness_decay,
                    concurrency=(async_concurrency if async_concurrency
                                 else max(clients_per_round, buffer_size)),
                    max_staleness=max_staleness, seed=seed),
                wire=WireConfig(dtype=wire_dtype, simulate=True),
                telemetry=tel, faults=faults)
        else:
            scheduler = CohortScheduler(
                population, clients_per_round, over_select=over_select,
                deadline=deadline, dropout_rate=dropout_rate, seed=seed)
            executor = (ShardedExecutor(microbatch=runtime_microbatch)
                        if runtime_executor == "sharded"
                        else SerialExecutor(microbatch=runtime_microbatch))
            engine = FederationEngine(
                cfg, sc, task="cls", comm_mode=comm_mode, executor=executor,
                wire=WireConfig(dtype=wire_dtype, simulate=wire_simulate),
                telemetry=tel, faults=faults, quorum=quorum)
            n_units = enumerate_units(state.peft).n_units
        client_data = [ClientDataset(x_tr, y_tr, population.shard(c))
                       for c in range(min(total_clients, 8))]
    else:
        parts = dirichlet_partition(y_tr, total_clients, dirichlet_alpha,
                                    seed=seed)
        client_data = [ClientDataset(x_tr, y_tr, idx) for idx in parts]

    if engine is None:
        step_fn, kind = build_round_step(cfg, sc, method)
        # the round state is threaded round-to-round and never re-read, so
        # its buffers update in place (CPU may decline — that is fine)
        step_fn = jax.jit(step_fn, donate_argnums=(0,))
        if kind == "zo":
            state = init_zo_state(state)

    eval_logits = jax.jit(lambda st, xb: cls_logits(
        cfg, st.base, st.peft, {"tokens": xb}))

    def the_state(s):
        return s.inner if isinstance(s, ZOState) else s

    def eval_personalized():
        st = the_state(state)
        return personalized_accuracy(cfg, st, client_data, x_tr, y_tr, rng)

    history = []
    bytes_up_total = bytes_down_total = 0
    start_round = 0
    if resume:
        # crash-safe resume: the manifest carries everything the loop
        # consumes host-side (round idx, host RNG state, history, byte
        # totals); the jitted round key is fold_in(PRNGKey(seed),
        # round_idx), so restoring the state + round index replays the
        # remaining trajectory bit-identically
        from repro.checkpoint import load_checkpoint
        if not checkpoint_dir:
            raise ValueError("--resume requires --checkpoint-dir")
        state, man = load_checkpoint(checkpoint_dir, state)
        if man.algo_seed != seed:
            raise ValueError(f"checkpoint seed {man.algo_seed} != run seed "
                             f"{seed}: refusing to splice trajectories")
        start_round = man.round_idx
        history = list(man.history)
        bytes_up_total = int(man.extra.get("bytes_up_total", 0))
        bytes_down_total = int(man.extra.get("bytes_down_total", 0))
        if man.rng_state is not None:
            rng.bit_generator.state = man.rng_state
        if async_mode:
            # async determinism rides on the virtual-time snapshot: the
            # event heap (in-flight frames byte-for-byte), the staleness
            # buffer, the clock, and the dispatch counter
            from repro.checkpoint import decode_async_snapshot
            if "async" not in man.extra:
                raise ValueError("--async --resume needs a checkpoint "
                                 "written by an async run (no snapshot in "
                                 "the manifest)")
            engine.restore(decode_async_snapshot(man.extra["async"]))
        log(f"[{method}] resumed from {checkpoint_dir} at round "
            f"{start_round}")

    def maybe_checkpoint(r):
        if not checkpoint_dir:
            return
        if (r + 1) % max(1, checkpoint_every) != 0 and r != rounds - 1:
            return
        from repro.checkpoint import encode_async_snapshot, save_checkpoint
        extra = {"bytes_up_total": bytes_up_total,
                 "bytes_down_total": bytes_down_total}
        if async_mode:
            extra["async"] = encode_async_snapshot(engine.snapshot())
        save_checkpoint(
            checkpoint_dir, state, round_idx=r + 1, algo_seed=seed,
            rng_state=rng.bit_generator.state, history=history,
            extra=extra)

    probe = MemoryProbe(tel) if tel.enabled else None
    t0 = time.time()
    if start_round >= rounds:
        # the checkpoint already covers the whole run; only the final
        # personalized eval may be outstanding
        if history and "personalized_acc" not in history[-1]:
            history[-1]["personalized_acc"] = eval_personalized()
            log(f"[{method}] personalized_acc="
                f"{history[-1]['personalized_acc']:.4f}")
        return history
    profiling = (jax.profiler.trace(profile_dir) if profile_dir
                 else contextlib.nullcontext())
    with profiling:
        for r in range(start_round, rounds):
            t_round = time.perf_counter()
            compiles_before = compile_stats()["compiles"] if tel.enabled else 0
            if engine is not None and async_mode:
                state, metrics, report = engine.run_version(state, batch_size)
                # async reports carry ENGINE-LIFETIME byte totals (restored
                # across resume by the snapshot) — assign, don't accumulate
                bytes_up_total = report.bytes_up
                bytes_down_total = report.bytes_down
            elif engine is not None:
                plan = scheduler.plan_round(r, n_units, sc.seed)
                bx, by = scheduler.round_batch(plan, batch_size)
                state, metrics, report = engine.run_round(
                    state, plan, {"tokens": jnp.asarray(bx),
                                  "labels": jnp.asarray(by)})
                bytes_up_total += report.bytes_up
                bytes_down_total += report.bytes_down
            else:
                with tel.span("train.prep", round=r):
                    chosen = sample_clients(rng, total_clients,
                                            clients_per_round)
                    bx, by = stack_client_batches(
                        [client_data[c] for c in chosen], rng, batch_size)
                    batch = {"tokens": jnp.asarray(bx),
                             "labels": jnp.asarray(by)}
                with tel.span("train.dispatch", round=r):
                    state, metrics = step_fn(state, batch)
                if tel.enabled:
                    # engine emits "round" events itself on the runtime path;
                    # the in-process path emits its own here (one per round)
                    with tel.span("train.loss_read", round=r):
                        loss = float(metrics["loss"])
                    ev = {"round": r, "method": method, "loss": loss,
                          "wall_s": round(time.perf_counter() - t_round, 6)}
                    compiles = compile_stats()["compiles"] - compiles_before
                    if compiles:
                        ev["compiles"] = compiles
                    for k in ("jvp_abs_mean", "delta_norm"):
                        if k in metrics:
                            ev[k] = float(metrics[k])
                    if "fused_route" in metrics:
                        ev["route"] = ("fused" if float(metrics["fused_route"])
                                       else "standard")
                    tel.event("round", **ev)
            if probe is not None and r == 0:
                probe.sample("post_round_1")
            if (r + 1) % eval_every == 0 or r == rounds - 1:
                st = the_state(state)
                accs = []
                for i in range(0, min(len(x_te), 512), 64):
                    lg = eval_logits(st, jnp.asarray(x_te[i:i + 64]))
                    accs.append(np.asarray(
                        accuracy_from_logits(lg, jnp.asarray(y_te[i:i + 64]))))
                acc = float(np.mean(accs))
                entry = {"round": r + 1, "acc": acc,
                         "loss": float(metrics["loss"]),
                         "t": time.time() - t0}
                if "fused_route" in metrics:
                    entry["route"] = ("fused" if float(metrics["fused_route"])
                                      else "standard")
                extra = ""
                if engine is not None and async_mode:
                    entry["bytes_up"] = bytes_up_total
                    entry["bytes_down"] = bytes_down_total
                    extra = (f" up={bytes_up_total/1e6:.2f}MB "
                             f"sim_t={report.sim_time_s:.0f}s "
                             f"staleness={np.mean(report.staleness):.1f} "
                             f"util={report.utilization:.2f}")
                elif engine is not None:
                    entry["bytes_up"] = bytes_up_total
                    entry["bytes_down"] = bytes_down_total
                    extra = (f" up={bytes_up_total/1e6:.2f}MB "
                             f"down={bytes_down_total/1e6:.2f}MB "
                             f"survivors={report.n_validated}/"
                             f"{report.cohort_size}")
                    if report.round_skipped:
                        extra += " [below quorum: round skipped]"
                history.append(entry)
                if tel.enabled:
                    ev = {k: v for k, v in entry.items() if k != "t"}
                    ev["round"] = r   # 0-based, matching the "round" events
                    tel.event("eval", **ev)
                log(f"[{method}] round {r+1:4d} "
                    f"loss={float(metrics['loss']):.4f} "
                    f"test_acc={acc:.4f} ({time.time()-t0:.0f}s){extra}")
            maybe_checkpoint(r)
    history[-1]["personalized_acc"] = eval_personalized()
    if tel.enabled:
        probe.sample("end_of_run")
        tel.event("personalized_eval",
                  personalized_acc=history[-1]["personalized_acc"])
    log(f"[{method}] personalized_acc={history[-1]['personalized_acc']:.4f}")
    return history


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="roberta-large-lora")
    ap.add_argument("--task", default="sst2")
    ap.add_argument("--method", default="spry", choices=METHODS)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--total-clients", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--local-iters", type=int, default=1)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--server-lr", type=float, default=None)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--jvp-clip", type=float, default=None)
    ap.add_argument("--tangent-batch", type=int, default=None,
                    help="tangents per batched estimator pass (None = all "
                         "K; 1 = sequential; 1<b<K = scanned groups of b)")
    ap.add_argument("--fused-contraction", action="store_true",
                    help="contract final-mixer-site tangents against the "
                         "post-head cotangent in-kernel (effective for "
                         "losses that declare a fused site)")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full (unreduced) architecture")
    ap.add_argument("--runtime", action="store_true",
                    help="drive rounds through the federation runtime "
                         "(fl/runtime: scheduler -> executor -> engine)")
    ap.add_argument("--runtime-executor", default="serial",
                    choices=("serial", "sharded"))
    ap.add_argument("--runtime-microbatch", type=int, default=None,
                    help="clients per executor vmap chunk (None = whole "
                         "cohort; finite = streaming aggregation)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="event-driven FedBuff engine: clients stream "
                         "updates as they finish; the server aggregates "
                         "the first --buffer-size validated arrivals with "
                         "staleness-weighted combination (implies "
                         "--runtime)")
    ap.add_argument("--buffer-size", type=int, default=4,
                    help="async: validated arrivals per server step (B)")
    ap.add_argument("--staleness-decay", type=float, default=0.5,
                    help="async: a in w = 1/(1+s)^a (0 = ignore staleness)")
    ap.add_argument("--async-concurrency", type=int, default=None,
                    help="async: clients kept in flight (default: "
                         "max(--clients, --buffer-size))")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="async: drop updates staler than this many "
                         "versions (None = never)")
    ap.add_argument("--over-select", type=float, default=1.0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="straggler cutoff seconds (None = 90%% quantile)")
    ap.add_argument("--dropout-rate", type=float, default=0.0)
    ap.add_argument("--wire-dtype", default="fp32",
                    choices=("fp32", "bf16", "fp16"))
    ap.add_argument("--wire-simulate", action="store_true",
                    help="route every update through a serialized frame")
    ap.add_argument("--faults", default=None,
                    help="chaos schedule: 'mild'/'aggressive' preset or "
                         "'crash_rate=0.1,corrupt_rate=0.2,...' (implies "
                         "--wire-simulate; requires --runtime)")
    ap.add_argument("--quorum", type=float, default=None,
                    help="min validated survivors per round: fraction of "
                         "the requested cohort if <= 1.0, else an absolute "
                         "count; below quorum the cohort is re-extended or "
                         "the server step is skipped")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="crash-safe checkpoint directory (atomic state + "
                         "manifest written every --checkpoint-every rounds)")
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint-dir's manifest, "
                         "replaying the remaining rounds bit-identically")
    ap.add_argument("--out", default=None)
    ap.add_argument("--telemetry", default="telemetry.jsonl",
                    help="JSONL event-log path (machine-readable round "
                         "reporting, on by default; 'off' disables)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON (Perfetto-loadable) "
                         "of the run's spans to this path")
    ap.add_argument("--prom-out", default=None,
                    help="Prometheus textfile-collector snapshot path")
    ap.add_argument("--profile-dir", default=None,
                    help="record a profiler (xplane) trace of the round "
                         "loop into this directory: the train.* spans on "
                         "the host line beside the device operations")
    args = ap.parse_args()
    tel = make_telemetry(
        jsonl=None if args.telemetry in ("off", "none", "") else args.telemetry,
        prometheus=args.prom_out, run_id=f"train-{args.method}-{args.seed}",
        workload="train")
    hist = run_training(arch=args.arch, task=args.task, method=args.method,
                        rounds=args.rounds, clients_per_round=args.clients,
                        total_clients=args.total_clients,
                        batch_size=args.batch_size,
                        local_iters=args.local_iters, local_lr=args.lr,
                        server_lr=args.server_lr, dirichlet_alpha=args.alpha,
                        seed=args.seed, reduced=not args.full_size,
                        k_perturbations=args.k, jvp_clip=args.jvp_clip,
                        tangent_batch=args.tangent_batch,
                        fused_contraction=args.fused_contraction,
                        runtime=args.runtime,
                        runtime_executor=args.runtime_executor,
                        runtime_microbatch=args.runtime_microbatch,
                        over_select=args.over_select, deadline=args.deadline,
                        dropout_rate=args.dropout_rate,
                        wire_dtype=args.wire_dtype,
                        wire_simulate=args.wire_simulate,
                        telemetry=tel, faults=args.faults,
                        quorum=args.quorum,
                        checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every,
                        resume=args.resume,
                        async_mode=args.async_mode,
                        buffer_size=args.buffer_size,
                        staleness_decay=args.staleness_decay,
                        async_concurrency=args.async_concurrency,
                        max_staleness=args.max_staleness,
                        profile_dir=args.profile_dir)
    if tel.enabled:
        if args.trace_out:
            tel.export_chrome_trace(args.trace_out)
        tel.close()
        print(f"[telemetry] events -> {args.telemetry}"
              + (f"  trace -> {args.trace_out}" if args.trace_out else ""))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
