"""Benchmark of the Spry federated round on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout. The workload names a cell of
``BENCHMARK.json``; its configuration, traffic, reference, limits and
per-layer metric readers are files under ``bench/`` found by name (see
``bench/cell.py``).

One run: the frozen base and the trainable tree are made on the device from
the seed (``bench/reference/<family>.py``); the program's jitted round step
(``repro.launch.train.build_round_step``, state donated, as
``run_training`` jits it) is compiled ahead of time and driven through the
first rounds on rows that all differ; then the measured window drives the
same compiled step for ``--seconds``, each round sampling its clients,
stacking their batches, calling the step and reading the round's loss on
the host. After the window the program's state is freed and the plain
reference follows the first rounds; ``bench/correct.py`` compares.

The last line of standard output is one JSON object. With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. A run with no TPU, or on
a device kind without published peaks, exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import cell as cells  # noqa: E402
import correct  # noqa: E402
from traffic.generator import Rounds  # noqa: E402

GIB = 2 ** 30


def say(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(root):
    """JAX's persistent cache at the fixed path ``<root>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_chip(chips):
    """(device, peaks); exits when JAX has no TPU or too few of them."""
    import jax
    from peaks import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips needed, {len(devs)} present")
    return devs[0], peaks_for(devs[0].device_kind)


def model_config(cfg):
    """The program's ModelConfig from the configuration file."""
    from repro.configs.base import ModelConfig, SSMConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    if kw.get("ssm"):
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def weight_key(seed):
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Program:
    """The system under test for one cell: the round step as
    ``run_training`` builds and jits it, and its state."""

    def __init__(self, cell, seed, wrap_step=None):
        import jax
        from repro.core import init_state
        from repro.launch.train import build_round_step

        cfg = cell.cfg
        self.cell, self.seed = cell, seed
        self.model_cfg = model_config(cfg)
        self.sc = self.spry_config(cell)
        ref = cell.reference
        kb, kp = jax.random.split(weight_key(seed))
        # the weights come from the benchmark, made on the device in one call
        self.state = jax.jit(lambda kb, kp: init_state(
            ref.init_base(cfg, kb), ref.init_peft(cfg, kp)))(kb, kp)
        step = build_round_step(self.model_cfg, self.sc, "spry")[0]
        if wrap_step is not None:
            step = wrap_step(step)
        self.jitted = jax.jit(step, donate_argnums=(0,))
        tr = cell.traffic
        shape = (tr["clients_per_round"], tr["batch_size"], tr["seq_len"])
        batch = {"tokens": jax.ShapeDtypeStruct(shape, "int32"),
                 "labels": jax.ShapeDtypeStruct(shape[:2], "int32")}
        self.compiled = self.jitted.lower(self.state, batch).compile()

    @staticmethod
    def spry_config(cell):
        """The program's SpryConfig for the cell's traffic and adapters."""
        from repro.launch.train import spry_config
        cfg, tr = cell.cfg, cell.traffic
        return spry_config(
            "spry", local_lr=tr["local_lr"], server_lr=tr["server_lr"],
            n_clients_per_round=tr["clients_per_round"],
            n_total_clients=tr["total_clients"],
            k_perturbations=tr["k_perturbations"],
            fused_contraction=tr["fused_contraction"],
            dirichlet_alpha=tr["dirichlet_alpha"], seed=tr["algo_seed"],
            lora_rank=cfg["lora_rank"], lora_alpha=cfg["lora_alpha"],
            lora_targets=tuple(cfg["lora_targets"]))

    def free(self):
        self.state = self.compiled = self.jitted = None

    def round(self, batch):
        self.state, metrics = self.compiled(self.state, batch)
        return metrics


def memory_bytes(device):
    """The device's peak memory: buffers in use plus what the runtime
    reserved for compiled programs' temporaries (on a TPU the temporaries
    are not counted in ``peak_bytes_in_use``). None where the backend keeps
    no statistics."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def to_device(tokens, labels):
    import jax.numpy as jnp
    return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}


def first_rounds(program, traffic_rounds):
    """Drive the compiled step through the traffic's first rounds. Returns
    their batches (host) and what ``correct`` compares: the rounds' losses,
    the first round's aggregated update and the trainable tree's change."""
    import jax
    import numpy as np
    from reference.spry import B1
    batches, losses, grad = [], [], None
    for r in range(program.cell.traffic["first_rounds"]):
        host = traffic_rounds.next()
        batches.append(host)
        metrics = program.round(to_device(*host))
        losses.append(float(metrics["loss"]))
        if r == 0:
            # FedYogi's first moment after one step is (1 - b1) * update
            grad = jax.tree.map(lambda m: m / (1 - B1),
                                jax.device_get(program.state.server.m))
    change = jax.tree.map(np.subtract, jax.device_get(program.state.peft),
                          peft0_of(program.cell, program.seed))
    return batches, {"losses": losses, "grad": grad, "change": change}


def reference_rounds(cell, seed, batches, mm=None, alter=None):
    """The plain reference over the same rounds: (losses, first round's
    update, change of the trainable tree). ``mm`` replaces its matrix
    product (the control); ``alter(host batch) -> host batch`` changes its
    input (the half-batch fault)."""
    import jax
    import jax.numpy as jnp
    from reference import spry as rspry
    cfg, tr, ref = cell.cfg, cell.traffic, cell.reference
    kb, kp = jax.random.split(weight_key(seed))
    base = jax.jit(lambda k: ref.init_base(cfg, k))(kb)
    peft0 = jax.jit(lambda k: ref.init_peft(cfg, k))(kp)
    kw = {} if mm is None else {"mm": mm}
    client = rspry.make_client(
        lambda b, p, x: ref.loss(cfg, b, p, x, **kw),
        tr["k_perturbations"], tr["local_lr"])
    peft = peft0
    server = (jax.tree.map(jnp.zeros_like, peft),) * 2
    losses, grad = [], None
    for r, host in enumerate(batches):
        if alter is not None:
            host = alter(host)
        loss, delta, peft, server = rspry.round_step(
            client, base, peft, server, r, to_device(*host),
            seed=tr["algo_seed"], server_lr=tr["server_lr"])
        losses.append(loss)
        if r == 0:
            grad = jax.device_get(delta)
    change = jax.device_get(jax.tree.map(jnp.subtract, peft, peft0))
    del base
    return {"losses": losses, "grad": grad, "change": change}


def peft0_of(cell, seed):
    import jax
    _, kp = jax.random.split(weight_key(seed))
    return jax.device_get(jax.jit(
        lambda k: cell.reference.init_peft(cell.cfg, k))(kp))


@dataclasses.dataclass
class Window:
    rounds: int = 0
    failed: int = 0
    seconds: float = 0.0
    prep_s: float = 0.0
    first_round: int = 0


def window(program, traffic_rounds, seconds, first_round, annotate):
    """Closed-loop rounds for ``seconds``: prep, dispatch, loss read."""
    w = Window(first_round=first_round)
    t0 = time.perf_counter()
    while True:
        ta = time.perf_counter()
        with annotate("prep"):
            batch = to_device(*traffic_rounds.next())
        tb = time.perf_counter()
        with annotate("dispatch"):
            metrics = program.round(batch)
        with annotate("loss_read"):
            loss = float(metrics["loss"])
        w.prep_s += tb - ta
        w.rounds += 1
        w.failed += not math.isfinite(loss)
        if time.perf_counter() - t0 >= seconds:
            break
    w.seconds = time.perf_counter() - t0
    return w


def run(root, workload, seed, seconds, trace, *, require_chip=True,
        wrap_step=None, bench_dir=BENCH_DIR):
    """One run of one cell. Returns (result dict, lines for stderr)."""
    cell = cells.Cell(root, workload, bench_dir)
    enable_compile_cache(root)
    import jax
    if require_chip:
        device, peaks = check_chip(cell.chips)
    else:
        device, peaks = jax.devices()[0], None
    limits = correct.load_limits(bench_dir, workload)
    tr = cell.traffic

    program = Program(cell, seed, wrap_step)
    jax.block_until_ready(program.state)
    peak_weights = memory_bytes(device)
    traffic_rounds = Rounds(tr, cell.cfg["vocab"], cell.cfg["n_classes"],
                            seed)
    batches, prog = first_rounds(program, traffic_rounds)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - T_START
    w = window(program, traffic_rounds, seconds, tr["first_rounds"], annotate)
    if trace:
        jax.profiler.stop_trace()
    peak = memory_bytes(device)
    if peak is None and require_chip:
        raise SystemExit("the device reports no peak_bytes_in_use")
    lines = [f"device memory peak (in use + reserved) once the weights "
             f"were made {peak_weights}, after the window {peak}"]
    if peak is not None and peak == peak_weights:
        lines.append("the peak was set by the weights, not by the step")

    run_info = dict(cell=cell, seed=seed, window=w, peaks=peaks,
                    program=program, device=device)
    if trace:
        import trace_reduce
        run_info["trace"] = trace_reduce.reduce_dir(
            trace_dir, program.compiled.as_text())
        shutil.rmtree(trace_dir, ignore_errors=True)
        per_layer = {}
        for m in cell.metrics("per_layer"):
            value = cell.reader(m["name"]).read(run_info)
            if value is not None:
                per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
        metrics = per_layer
    else:
        tokens = w.rounds * math.prod(
            tr[k] for k in ("clients_per_round", "batch_size", "seq_len"))
        metrics = {
            "train_tokens_per_s": {"value": tokens / w.seconds,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        if peak is not None:
            metrics["peak_hbm_gib"] = {"value": peak / GIB, "unit": "GiB"}
        metrics = {m["name"]: metrics[m["name"]]
                   for m in cell.metrics("end_to_end") if m["name"] in metrics}

    # free the program's state before the reference runs on the chip
    run_info.pop("program")
    program.free()
    del program
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_rounds(cell, seed, batches)
    values = correct.numbers(prog, ref)
    ok, checks = correct.judge(values, limits)
    lines.append(f"reference: {time.perf_counter() - t_ref:.1f} s; losses "
                 f"program {prog['losses']} reference {ref['losses']}")

    result = {
        "correct": ok and w.failed == 0,
        "attempted": w.rounds,
        "failed": w.failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if trace:
        t = run_info["trace"]
        result["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        result["breakdown"] = t.breakdown()
    result["checks"] = checks
    lines += [f"check {n}: {c['value']!r} limit {c['limit']!r}"
              for n, c in checks.items()]
    return result, lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    result, lines = run(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    for line in lines:
        say(line)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
