"""Readings that the limits of ``correct`` are set from, for one cell, in
one process:

    python3 bench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--out readings.jsonl]

For every seed: the program's first rounds against the plain reference
(the lower readings). For every control seed besides: the control (the
reference with float8 matrix products) in the program's place, the
half-batch fault (the reference on half of each batch) and the altered
answer (the reference's largest unit change doubled), each against the
reference (the upper readings). A state left unchanged reads 1 by the
measure and needs no run. The benchmark's own runs do not run these.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import run as bench
import correct
import faults
from cell import Cell
from traffic.generator import Rounds


def program_first_rounds(cell, seed):
    tr = cell.traffic
    program = bench.Program(cell, seed)
    batches, prog = bench.first_rounds(
        program, Rounds(tr, cell.cfg["vocab"], cell.cfg["n_classes"], seed))
    program.free()
    gc.collect()
    return batches, prog


def unit_ratios(prog, ref):
    """Program over reference norm of each unit of the first round's
    update, and the first round's loss gap: where a gap comes from."""
    got, want = (correct.unit_norms(x["grad"]) for x in (prog, ref))
    return {"first_loss_gap": abs(prog["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_ratio": {k: got[k] / max(want[k], 1e-30) for k in want},
            "grad_ref": want}


def altered(ref):
    import jax
    import jax.numpy as jnp
    zero = jax.tree.map(jnp.zeros_like, ref["change"])
    return dict(ref, change=jax.device_get(
        faults.double_largest_unit(zero, ref["change"])))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="key=JSON value: change the configuration for a "
                         "look at the cause of a reading (e.g. "
                         "n_layers=4, param_dtype=\"float32\")")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    cell = Cell(bench.ROOT, args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        cell.cfg[key] = json.loads(value)
    bench.enable_compile_cache(bench.ROOT)
    bench.check_chip(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in seeds + sorted(control - set(seeds)):
        t0 = time.perf_counter()
        batches, prog = program_first_rounds(cell, seed)
        ref = bench.reference_rounds(cell, seed, batches)
        row = {"workload": cell.name, "seed": seed, "set": args.set,
               "program": correct.numbers(prog, ref),
               "units": unit_ratios(prog, ref),
               "losses": {"program": prog["losses"],
                          "reference": ref["losses"]}}
        if seed in control:
            ctl = bench.reference_rounds(cell, seed, batches,
                                         mm=correct.fp8_mm)
            row["control"] = correct.numbers(ctl, ref)
            row["losses"]["control"] = ctl["losses"]
            half = bench.reference_rounds(
                cell, seed, batches, alter=lambda b: faults.half_batch(*b))
            row["half_batch"] = correct.numbers(half, ref)
            row["altered"] = correct.numbers(altered(ref), ref)
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
