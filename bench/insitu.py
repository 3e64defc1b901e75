"""Every kernel call of a cell's round step checked where it runs, against
the kernel's own float32 oracle (``repro/kernels/*/ref.py``) on the same
operands; a copy of the in situ checks of ``chip_smoke.py``, run apart from
the benchmark:

    python3 bench/insitu.py --workload <name> [--seed <n>]

The dispatch layer's kernel entry points are wrapped so that each call also
runs its oracle at "highest" precision and records, through a host
callback, max |kernel - oracle| / max |oracle|. One round of the cell's
step, compiled with the wrapped entry points, is run from the benchmark's
weights and traffic. The wrapped program is not the timed one, and this
check does not decide ``correct``: it says which kernel to look at when a
comparison with the plain reference fails.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import run as bench
from cell import Cell
from traffic.generator import Rounds

# largest relative error allowed per entry point (chip_smoke.KERNEL_TOL): the
# wkv6 kernels are float32 throughout; the LoRA kernels write bf16 and feed
# the MXU bf16-rounded operands at the default precision
KERNEL_TOL = {"wkv6_scan": 1e-4, "wkv6_scan_mt_tangents": 1e-4,
              "wkv6_scan_mt_jvps": 1e-4, "lora_dual_mt_tangents": 2e-2}


@contextlib.contextmanager
def checked_kernels(errs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispatch
    from repro.kernels.lora_dual.ref import lora_dual_mt_ref
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
        def wrapped(*args, **kw):
            out = kernel[name](*args, **kw)
            with jax.default_matmul_precision("highest"):
                record(name, out, oracle(*args, **kw))
            return out
        return wrapped

    def lora_mt(x, xds, w, a, ads, b, bds, *, scale, interpret):
        T, K = ads.shape[0], x.shape[-1]
        xd2 = None if xds is None else xds.reshape(T, -1, K)
        yds = lora_dual_mt_ref(*f32(x.reshape(-1, K), xd2, w, a, ads, b, bds),
                               scale)[1]
        return yds.reshape((T,) + x.shape[:-1] + w.shape[-1:])

    oracles = {
        "wkv6_scan": lambda *a, interpret: wkv6_scan_ref(*f32(*a))[0],
        "wkv6_scan_mt_tangents":
            lambda *a, interpret: wkv6_scan_mt_ref(*f32(*a))[1],
        "wkv6_scan_mt_jvps":
            lambda *a, interpret: wkv6_scan_mt_jvps_ref(*f32(*a)),
        "lora_dual_mt_tangents": lora_mt,
    }
    for name in KERNEL_TOL:
        setattr(dispatch, name, check(name, oracles[name]))
    try:
        yield errs
    finally:
        for name, fn in kernel.items():
            setattr(dispatch, name, fn)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    import jax
    cell = Cell(bench.ROOT, args.workload)
    bench.enable_compile_cache(bench.ROOT)
    bench.check_chip(cell.chips)
    tr = cell.traffic
    rounds = Rounds(tr, cell.cfg["vocab"], cell.cfg["n_classes"], args.seed)
    errs = {}
    with checked_kernels(errs):
        program = bench.Program(cell, args.seed)
        jax.block_until_ready(program.round(bench.to_device(*rounds.next())))
    jax.effects_barrier()
    ok = bool(errs)
    report = {}
    for name, e in sorted(errs.items()):
        report[name] = {"calls": len(e), "max_rel_err": max(e),
                        "tolerance": KERNEL_TOL[name]}
        ok &= max(e) <= KERNEL_TOL[name]
    print(json.dumps({"workload": cell.name, "ok": ok, "kernels": report}))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
