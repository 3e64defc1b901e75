"""On-chip smoke test: the Spry round and the serving engine on one TPU chip
at the full width of rwkv6-1.6b, through the entry points a user calls.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # a 2x2 host: sharded vs serial round

Phases, all in this one process (the chip belongs to one process):

  device   a TPU is attached and the kernel dispatch resolves to 'pallas'
  train    ``repro.launch.train.run_training`` at full width (random bf16
           base from a seed): Spry rounds at K=1, then at K=4 with fused
           contraction; every round's loss is finite
  kernels  the compiled round steps hold a ``tpu_custom_call`` for each
           kernel family on their route (lora_dual, wkv6_scan, and the
           wkv6 jvp-contraction epilogue on the fused route)
  compare  every kernel call of the full-width round (K=1; K=4 fused)
           against its f32 oracle on the same operands (KERNEL_TOL); then
           the round's clients and aggregation on one state and seed under
           the 'pallas' and the 'jnp' backend, full width and COMPARE_LAYERS
           deep: K jvp scalars per client and the aggregated LoRA update
           agree within REL_TOL
  serve    requests through ``ServingEngine`` as ``repro.launch.serve
           --full-size --engine N`` runs them, each ``lora_dual_multi`` call
           checked against its oracle; COMPARE_LAYERS deep, the engine's ids
           are the greedy argmax of per-request decoding fed the same ids,
           or near-ties
  memory   the device's peak_bytes_in_use

``--four-chips`` runs only one federation-runtime round through
``ShardedExecutor`` over every chip and the same round through
``SerialExecutor`` on one, full width and COMPARE_LAYERS deep, and compares
the aggregated updates.

Any failure exits non-zero before the last line. The last line of standard
output is one JSON object: {"ok": true, "device": {platform, kind, count}}.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "rwkv6-1.6b"
TASK = "sst2"
SEED = 0
# Every kernel call of the full-width round, and of the serving engine's
# decode, is checked in situ against its ref.py oracle run in f32 at full
# precision on the same operands: largest max |kernel - oracle| over
# max |oracle| allowed per entry point. The wkv6 kernels are f32 throughout
# (1e-7 on a v5e). The LoRA kernels write bf16 (2^-9 relative rounding)
# and, at the TPU's default precision, feed the MXU bf16-rounded f32
# operands (7e-3 on a v5e). A wrong kernel errs by order 1.
KERNEL_TOL = {"wkv6_scan": 1e-4, "wkv6_scan_mt_tangents": 1e-4,
              "wkv6_scan_mt_jvps": 1e-4, "lora_dual_mt_tangents": 2e-2,
              "lora_dual_multi": 2e-2}
# Largest relative error (max |pallas - jnp| over max |jnp|) allowed between
# the kernel route and the jnp route, for the jvp scalars and the aggregated
# update of one round at full width and COMPARE_LAYERS deep. Both routes
# read a bf16 base and keep bf16 activations and tangents, rounded at
# different points, and each jvp is a heavily cancelling sum of bf16
# tangent entries: two programs of the same math (the jnp route at K=1 and
# at K=4 fused) give losses 1% apart on a v5e at 2 layers and 9% apart
# at 24, and the two routes' jvps 5e-2 apart at 2 layers. REL_TOL is three
# times that; the in situ checks above are the tight test of the kernels.
REL_TOL = 0.15
COMPARE_LAYERS = 2
# Engine ids are checked against greedy decoding fed the same ids, at full
# width and COMPARE_LAYERS deep: an id other than the greedy argmax is a
# near-tie only if its logit is within NEAR_TIE x max |logit| of the top
# one. The engine's multi-adapter kernel and greedy's XLA projection round
# at different points, and the random model's 65536 logits hold near-ties;
# a wrong id is a draw from the whole vocabulary, whose logit sits order 1
# below the top. At 24 layers the two decoders' logits part too far for
# this test (gaps of 0.28 on a v5e), so there it only prints.
NEAR_TIE = 5e-2
REDUCED = False          # full width; the CPU rehearsal sets True
TRAIN = dict(arch=ARCH, task=TASK, method="spry", seed=SEED,
             clients_per_round=2, total_clients=2, batch_size=2,
             dirichlet_alpha=1.0)


def fail(msg):
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(a, b):
    import numpy as np
    a = np.concatenate([np.ravel(np.asarray(x, np.float64)) for x in a])
    b = np.concatenate([np.ravel(np.asarray(x, np.float64)) for x in b])
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def kernel_calls(hlo_text):
    """{ops entry point: count} of the ``tpu_custom_call``s in compiled HLO
    text, named by the innermost ``jit(...)`` of each call's op_name (the
    kernels' jitted ops.py wrappers)."""
    import re
    counts = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        names = re.findall(r"jit\((\w+)\)", op.group(1)) if op else []
        name = names[-1] if names else "?"
        counts[name] = counts.get(name, 0) + 1
    return counts


def check_device(n_chips):
    import jax
    devs = jax.devices()
    dev = devs[0]
    say(f"devices: {len(devs)} x {dev.platform} {dev.device_kind}")
    if dev.platform != "tpu":
        fail(f"no TPU: JAX sees {dev.platform!r} devices")
    if len(devs) < n_chips:
        fail(f"{n_chips} chips needed, {len(devs)} present")
    from repro.kernels import dispatch
    if dispatch.get_backend() != "pallas":
        fail(f"kernel dispatch resolved to {dispatch.get_backend()!r}")
    return dev


def train_phase():
    import math

    from repro.launch.train import run_training
    from repro.obs import make_telemetry
    from repro.obs.sinks import InMemorySink

    for k, fused, rounds in ((1, False, 3), (4, True, 2)):
        tel = make_telemetry(in_memory=True, run_id=f"smoke-k{k}",
                             workload="train")
        run_training(**TRAIN, reduced=REDUCED, rounds=rounds,
                     eval_every=rounds,
                     k_perturbations=k, fused_contraction=fused,
                     telemetry=tel, log=lambda m: say(f"train: {m}"))
        sink = next(s for s in tel.sinks if isinstance(s, InMemorySink))
        losses = [e["loss"] for e in sink.by_kind("round")]
        route = "fused" if fused else "standard"
        say(f"train K={k} {route}: round losses {losses}")
        if len(losses) != rounds or not all(map(math.isfinite, losses)):
            fail(f"K={k} {route}: round losses {losses}")


def _round_inputs(k, fused, clients=TRAIN["clients_per_round"],
                  n_layers=None):
    """Config, SpryConfig, state and one round's batch as run_training
    builds them, for ``clients`` clients (and ``n_layers`` layers if
    given)."""
    import jax
    import jax.numpy as jnp

    from repro.core import init_state
    from repro.launch.train import spry_config, task_config
    from repro.models import get_model
    from repro.peft import init_peft

    cfg, (x_tr, y_tr, _, _) = task_config(ARCH, TASK, SEED, REDUCED)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    M, B = clients, TRAIN["batch_size"]
    sc = spry_config("spry", n_clients_per_round=M,
                     n_total_clients=max(M, TRAIN["total_clients"]),
                     k_perturbations=k, fused_contraction=fused,
                     dirichlet_alpha=TRAIN["dirichlet_alpha"], seed=SEED)
    key = jax.random.PRNGKey(SEED)
    state = init_state(get_model(cfg).init_base(cfg, key),
                       init_peft(cfg, key, sc))
    batch = {"tokens": jnp.asarray(x_tr[:M * B].reshape(M, B, -1)),
             "labels": jnp.asarray(y_tr[:M * B].reshape(M, B))}
    return cfg, sc, state, batch, (x_tr, y_tr)


def kernels_phase():
    """Compile the round step as run_training jits it and count its kernels
    by family."""
    import jax

    from repro.launch.train import build_round_step

    for k, fused, need in ((1, False, ("lora_dual", "wkv6_scan")),
                           (4, True, ("lora_dual", "wkv6_scan",
                                      "wkv6_scan_mt_jvps"))):
        built = {}

        def inputs():
            cfg, sc, state, batch, _ = _round_inputs(k, fused)
            built.update(cfg=cfg, sc=sc)
            return state, batch

        shapes = jax.eval_shape(inputs)
        step = jax.jit(build_round_step(built["cfg"], built["sc"], "spry")[0],
                       donate_argnums=(0,))
        # persistent cache off: the HLO text read is this compile's own
        # output, not that of an executable read back from the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            hlo = step.lower(*shapes).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
        calls = kernel_calls(hlo)
        say(f"kernels K={k} {'fused' if fused else 'standard'}: "
            f"tpu_custom_call {calls}")
        for fam in need:
            if not any(n.startswith(fam) for n in calls):
                fail(f"K={k}: no tpu_custom_call of {fam}")


@contextlib.contextmanager
def checked_kernels(errs):
    """Route the dispatch layer's kernel entry points through wrappers that
    also run the kernel's ref.py oracle on the same operands, in f32 at
    full precision, and record max |kernel - oracle| / max |oracle| of
    every call (a host callback) in ``errs[name]``: each kernel call of a
    compiled round or decode step is checked where it runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.kernels.lora_dual.ref import lora_dual_mt_ref, lora_dual_multi_ref
    from repro.kernels.wkv6_scan.ref import (
        wkv6_scan_mt_jvps_ref, wkv6_scan_mt_ref, wkv6_scan_ref)

    def record(name, got, want):
        def host(g, w):
            g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
            errs.setdefault(name, []).append(
                float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30)))
        jax.debug.callback(host, got, want)

    def f32(*ts):
        return [None if t is None else t.astype(jnp.float32) for t in ts]

    kernel = {n: getattr(dispatch, n) for n in KERNEL_TOL}

    def check(name, oracle):
        def run(*args, **kw):
            out = kernel[name](*args, **kw)
            with jax.default_matmul_precision("highest"):
                record(name, out, oracle(*args, **kw))
            return out
        return run

    def lora_mt(x, xds, w, a, ads, b, bds, *, scale, interpret):
        T, K = ads.shape[0], x.shape[-1]
        xd2 = None if xds is None else xds.reshape(T, -1, K)
        yds = lora_dual_mt_ref(*f32(x.reshape(-1, K), xd2, w, a, ads, b, bds),
                               scale)[1]
        return yds.reshape((T,) + x.shape[:-1] + w.shape[-1:])

    def lora_multi(x, idx, w, a_stack, b_stack, *, scale, interpret):
        rows = jnp.broadcast_to(
            idx.reshape(idx.shape + (1,) * (x.ndim - 1 - idx.ndim)),
            x.shape[:-1]).reshape(-1)
        y = lora_dual_multi_ref(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                                rows, *f32(w, a_stack, b_stack), scale)
        return y.reshape(x.shape[:-1] + w.shape[-1:])

    oracles = {
        "wkv6_scan": lambda *a, interpret: wkv6_scan_ref(*f32(*a))[0],
        "wkv6_scan_mt_tangents":
            lambda *a, interpret: wkv6_scan_mt_ref(*f32(*a))[1],
        "wkv6_scan_mt_jvps":
            lambda *a, interpret: wkv6_scan_mt_jvps_ref(*f32(*a)),
        "lora_dual_mt_tangents": lora_mt,
        "lora_dual_multi": lora_multi,
    }
    for name in KERNEL_TOL:
        setattr(dispatch, name, check(name, oracles[name]))
    try:
        yield errs
    finally:
        for name, fn in kernel.items():
            setattr(dispatch, name, fn)


def check_kernel_errs(errs, need, what):
    for name in need:
        if name not in errs:
            fail(f"{what}: no call of {name} was checked")
    for name, e in sorted(errs.items()):
        say(f"{what}: {name}: {len(e)} calls, max rel err vs f32 oracle "
            f"{max(e):.3e} (tolerance {KERNEL_TOL[name]})")
        if not max(e) <= KERNEL_TOL[name]:
            fail(f"{what}: {name} disagrees with its oracle")


def _clients_and_update(cfg, sc, state, batch, backend):
    """One round's client side and aggregation (make_round_step's pieces)
    under ``backend``, traced and compiled anew: (K jvps per client, the
    aggregated LoRA update)."""
    import jax
    import jax.numpy as jnp

    from repro.core.assignment import (
        assignment_matrix, client_counts, enumerate_units)
    from repro.core.spry import aggregate_payloads, make_client_update_fn
    from repro.kernels import dispatch

    M = sc.n_clients_per_round

    def run(state, batch):
        index = enumerate_units(state.peft)
        mask = assignment_matrix(index.n_units, M, state.round_idx % M)
        round_key = jax.random.fold_in(jax.random.PRNGKey(sc.seed),
                                       state.round_idx)
        client = make_client_update_fn(cfg, sc, "cls")
        deltas, _, jvps = jax.vmap(
            lambda sid, row, cb: client(state.base, state.peft, round_key,
                                        sid, row, cb))(
            jnp.arange(M), mask, batch)
        return jvps, aggregate_payloads(state.peft, index, deltas,
                                        client_counts(mask), M)

    dispatch.set_backend(backend)
    try:
        return jax.device_get(jax.jit(run)(state, batch))
    finally:
        dispatch.set_backend(None)


def compare_phase():
    """Kernel route against oracles: every kernel call of the full-width,
    full-depth round (K=1, and K=4 fused) against its f32 oracle; then the
    round's jvps and update under 'pallas' vs 'jnp', COMPARE_LAYERS deep."""
    import jax

    for k, fused, need in ((1, False, ("lora_dual_mt_tangents", "wkv6_scan",
                                       "wkv6_scan_mt_tangents")),
                           (4, True, ("lora_dual_mt_tangents", "wkv6_scan",
                                      "wkv6_scan_mt_jvps"))):
        route = "fused" if fused else "standard"
        cfg, sc, state, batch, _ = _round_inputs(k, fused)
        with checked_kernels({}) as errs:
            _clients_and_update(cfg, sc, state, batch, "pallas")
        del state
        check_kernel_errs(errs, need, f"in situ K={k} {route}, "
                                      f"{cfg.n_layers} layers")

    for k, fused in ((1, False), (4, True)):
        route = "fused" if fused else "standard"
        cfg, sc, state, batch, _ = _round_inputs(k, fused,
                                                 n_layers=COMPARE_LAYERS)
        jp, dp = _clients_and_update(cfg, sc, state, batch, "pallas")
        jj, dj = _clients_and_update(cfg, sc, state, batch, "jnp")
        e_jvp = rel_err([jp], [jj])
        e_upd = rel_err(jax.tree.leaves(dp), jax.tree.leaves(dj))
        say(f"compare K={k} {route}, {cfg.n_layers} layers: jvps pallas "
            f"{jp.ravel().tolist()} jnp {jj.ravel().tolist()}")
        say(f"compare K={k} {route}: pallas vs jnp max rel err jvps "
            f"{e_jvp:.3e}, aggregated LoRA update {e_upd:.3e} "
            f"(tolerance {REL_TOL})")
        if not (e_jvp <= REL_TOL and e_upd <= REL_TOL):
            fail(f"K={k} {route}: pallas and jnp routes disagree")


def _teacher_forced(cfg, engine, outputs, reqs, prompt_len, steps):
    """Per-request greedy decoding (greedy_generate's B=1 path) fed the
    engine's ids: (steps where the engine's id is the greedy argmax, ids,
    largest top-logit gap of an engine id over max |logit|)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import (
        build_serve_fns, can_fuse_prefill, tokenwise_prefill)

    model = engine.model
    fns = build_serve_fns(cfg, model)
    n_equal, n_ids, worst = 0, 0, 0.0
    for req in reqs:
        peft = engine.adapters.store.load(req.adapter_id)
        prompt = jnp.asarray(req.prompt, jnp.int32).reshape(1, -1)
        cache = model.init_cache(cfg, 1, prompt_len + steps)
        if can_fuse_prefill(cfg, model, cache, prompt_len):
            logits, cache = fns["prefill"](engine.base, peft, cache, prompt)
        else:
            logits, cache = tokenwise_prefill(cfg, model, engine.base, peft,
                                              cache, prompt,
                                              decode=fns["decode"])
        for s, tok in enumerate(outputs[req.request_id]):
            lg = np.asarray(logits[0], np.float64)
            n_ids += 1
            n_equal += int(tok == int(np.argmax(lg)))
            worst = max(worst, (lg.max() - lg[tok]) / np.abs(lg).max())
            logits, cache = fns["decode"](engine.base, peft, cache,
                                          jnp.asarray([[tok]], jnp.int32),
                                          jnp.int32(prompt_len + s))
    return n_equal, n_ids, worst


def serve_phase():
    """Requests through the engine at full width and depth, every
    ``lora_dual_multi`` call checked against its oracle; then the engine's
    ids against per-request greedy decoding fed the same ids, gated
    COMPARE_LAYERS deep: each id is the greedy argmax or a near-tie."""
    from repro.configs import get_config, reduce_config
    from repro.launch.serve import run_engine

    cfg = get_config(ARCH)
    if REDUCED:
        cfg = reduce_config(cfg)
    prompt_len, steps = 16, 8
    for n_layers in (cfg.n_layers, COMPARE_LAYERS):
        cfg_n = dataclasses.replace(cfg, n_layers=n_layers)
        with checked_kernels({}) as errs:
            outputs, engine, reqs = run_engine(cfg_n, n_requests=4,
                                               prompt_len=prompt_len,
                                               steps=steps, max_batch=4,
                                               cache_capacity=4, seed=SEED)
        what = f"serve {n_layers} layers"
        check_kernel_errs(errs, ("lora_dual_multi",), f"{what} in situ")
        n_equal, n_ids, worst = _teacher_forced(cfg_n, engine, outputs,
                                                reqs, prompt_len, steps)
        gated = n_layers == COMPARE_LAYERS
        say(f"{what}: {len(reqs)} requests, {engine.steps} engine decode "
            f"steps; engine ids equal the teacher-forced greedy argmax at "
            f"{n_equal} of {n_ids} steps; largest top-logit gap of an "
            f"engine id {worst:.3e} of max |logit| "
            + (f"(tolerance {NEAR_TIE})" if gated else "(not gated)"))
        if gated and not worst <= NEAR_TIE:
            fail("engine ids differ from greedy ids beyond a near-tie")


def four_chip_phase():
    """One runtime round through ShardedExecutor over every chip and the
    same round through SerialExecutor on one device: the aggregated
    updates agree within REL_TOL."""
    import jax
    import jax.numpy as jnp

    from repro.core.assignment import enumerate_units
    from repro.fl.runtime import (
        ClientPopulation, CohortScheduler, FederationEngine, SerialExecutor,
        ShardedExecutor)

    n = len(jax.devices())
    cfg, sc, state, _, (x_tr, y_tr) = _round_inputs(
        1, False, clients=n, n_layers=COMPARE_LAYERS)
    # the FedAvg server step (lr 1) makes the round's update the aggregated
    # client delta itself, the sum the executors compute; FedYogi divides
    # each entry by its second moment, so an entry near zero turns a
    # rounding difference into an order-1 relative one
    sc = dataclasses.replace(sc, server_opt="fedavg", server_lr=1.0)
    population = ClientPopulation(x_tr, y_tr, n_clients=sc.n_total_clients,
                                  alpha=sc.dirichlet_alpha, seed=SEED)
    scheduler = CohortScheduler(population, n, seed=SEED)
    plan = scheduler.plan_round(0, enumerate_units(state.peft).n_units,
                                sc.seed)
    bx, by = scheduler.round_batch(plan, TRAIN["batch_size"])
    batch = {"tokens": jnp.asarray(bx), "labels": jnp.asarray(by)}
    new = {}
    for name, executor in (("sharded", ShardedExecutor()),
                           ("serial", SerialExecutor())):
        engine = FederationEngine(cfg, sc, task="cls", comm_mode="per_epoch",
                                  executor=executor)
        st, metrics, _ = engine.run_round(state, plan, batch)
        update = jax.tree.map(lambda a, b: a - b, st.peft, state.peft)
        new[name] = jax.device_get(update)
        say(f"four-chip {name}: loss {float(metrics['loss'])}")
    err = rel_err(jax.tree.leaves(new["sharded"]),
                  jax.tree.leaves(new["serial"]))
    say(f"four-chip: sharded over {n} chips vs serial on one, "
        f"{cfg.n_layers} layers: max rel err of the aggregated update "
        f"{err:.3e} (tolerance {REL_TOL})")
    if not err <= REL_TOL:
        fail("sharded and serial rounds disagree")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded-vs-serial runtime round on a "
                         "4-chip host")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")
    n_chips = 4 if args.four_chips else 1
    dev = check_device(n_chips)
    phases = ((four_chip_phase,) if args.four_chips else
              (train_phase, kernels_phase, compare_phase, serve_phase))
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        say(f"{phase.__name__}: {time.perf_counter() - t0:.1f} s host wall "
            "time, compiles included")

    import jax
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak is None:
        fail("device reports no peak_bytes_in_use")
    say(f"peak_bytes_in_use: {peak} ({peak / 2**30:.2f} GiB)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
