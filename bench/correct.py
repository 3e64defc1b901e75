"""How ``correct`` is decided: the program's first rounds against the plain
reference's, by numbers that each have a limit of their own.

Numbers (all relative, so a seed's scale cancels):

  loss_gap    max over the first rounds of |loss - ref| / |ref|
  grad_gap    the first round's aggregated update, the pseudo-gradient the
              server optimizer receives (read back from its first moment):
              worst unit of |norm - ref norm| / max(ref norm, median ref norm)
  change_gap  the trainable tree's change over the first rounds, worst unit
              as above; units whose reference pseudo-gradient is under a
              thousandth of the median unit's are left out

A unit is one layer's slice of a stacked LoRA factor, or one head leaf.
Limits live per workload in ``bench/limits/<workload>.json``; a number whose
limit is null there is not compared (PERF.md says why, with its readings).

The control is the reference computed with float8 matrix products (e4m3,
one scale per tensor), the precision below the configuration's bfloat16:
every product's operands and its result are rounded to it (``fp8_mm``), as
a program that kept its activations in float8 would round them.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
EXCLUDE_BELOW = 1e-3


def unit_norms(tree):
    """{unit name: L2 norm}; stacked leaves (under 'layers') split per
    layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = jax.tree_util.keystr(path)
        leaf = np.asarray(leaf, np.float64)
        if "'layers'" in name:
            for i in range(leaf.shape[0]):
                out[f"{name}[{i}]"] = float(np.linalg.norm(leaf[i]))
        else:
            out[name] = float(np.linalg.norm(leaf))
    return out


def worst_gap(got, want, skip=()):
    keys = [k for k in want if k not in skip]
    median = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], median, 1e-30)
               for k in keys)


def numbers(prog, ref):
    """``prog``/``ref``: dicts with 'losses' (first rounds), 'grad' (first
    round's aggregated update tree), 'change' (tree after the first rounds
    minus the initial tree)."""
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"])]
    g_ref = unit_norms(ref["grad"])
    median = float(np.median(list(g_ref.values())))
    skip = {k for k, v in g_ref.items() if v < EXCLUDE_BELOW * median}
    return {
        "loss_gap": max(losses),
        "grad_gap": worst_gap(unit_norms(prog["grad"]), g_ref),
        "change_gap": worst_gap(unit_norms(prog["change"]),
                                unit_norms(ref["change"]), skip),
    }


def load_limits(bench_dir, workload):
    path = os.path.join(bench_dir, "limits", f"{workload}.json")
    with open(path) as f:
        limits = json.load(f)
    missing = [n for n in NUMBERS if n not in limits]
    if missing:
        raise SystemExit(f"{path}: no limit for {missing}")
    compared = {n: float(limits[n]) for n in NUMBERS
                if limits[n] is not None}
    if not compared:
        raise SystemExit(f"{path}: no number is compared")
    return compared


def judge(values, limits):
    """(correct, {name: {'value', 'limit'}}) over the compared numbers; a
    number that is not finite fails."""
    checks = {n: {"value": values[n], "limit": lim}
              for n, lim in limits.items()}
    ok = all(np.isfinite(values[n]) and values[n] <= lim
             for n, lim in limits.items())
    return bool(ok), checks


E4M3_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    # clipped: a quotient a rounding above the largest finite e4m3 value
    # would otherwise convert to NaN (e4m3fn has no infinity)
    q = jnp.clip(x / scale, -E4M3_MAX, E4M3_MAX)
    return q.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_jvp
def fp8_round(x):
    """x rounded to float8 e4m3 with one scale for the tensor; its tangent is
    rounded the same way."""
    return _fp8(x)


@fp8_round.defjvp
def _fp8_round_jvp(primals, tangents):
    return _fp8(primals[0]), _fp8(tangents[0])


def fp8_mm(x, w):
    return fp8_round(jnp.matmul(fp8_round(x.astype(jnp.float32)),
                                fp8_round(w.astype(jnp.float32)),
                                precision="highest"))
