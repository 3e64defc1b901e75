"""AOT rehearsal: compile a cell's round step, at its real size, for a
described TPU v5e chip on a host without one, and print what the compiler
says of its memory and which kernels it holds.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <name> [--workload ...]

Nothing runs, so this gives no time; run it before a cell's first chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as bench  # noqa: E402
from cell import Cell  # noqa: E402
from hlo import kernel_calls  # noqa: E402


def compile_cell(cell, topology="v5e:2x2"):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import init_state
    from repro.kernels import dispatch
    from repro.launch.train import build_round_step

    tr, cfg, ref = cell.traffic, cell.cfg, cell.reference
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    chip = SingleDeviceSharding(topo.devices[0])
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda k: init_state(ref.init_base(cfg, k),
                                                ref.init_peft(cfg, k)), key)
    M, B, S = tr["clients_per_round"], tr["batch_size"], tr["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct((M, B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((M, B), jnp.int32)}
    place = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
    sc = bench.Program.spry_config(cell)
    dispatch.set_backend("pallas")
    try:
        step = build_round_step(bench.model_config(cfg), sc, "spry")[0]
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            place(state), place(batch)).compile()
    finally:
        dispatch.set_backend(None)
    return compiled


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    for name in args.workload:
        compiled = compile_cell(Cell(bench.ROOT, name))
        m = compiled.memory_analysis()
        print(json.dumps({
            "workload": name,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "kernel_calls": kernel_calls(compiled.as_text())}), flush=True)


if __name__ == "__main__":
    main()
