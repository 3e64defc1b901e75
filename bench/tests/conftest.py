"""CPU tests of the benchmark: run with

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

A 'tiny' cell (the rwkv6 family at 2 layers, width 256, float32) is written
next to a copy of bench/ in a temporary root, so that the harness runs end
to end on the CPU with its chip look skipped."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "counts"), os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
                  d_ff=512, vocab=512, param_dtype="float32")
TINY_TRAFFIC = dict(clients_per_round=4, batch_size=2, seq_len=16,
                    total_clients=8, pool_rows=256)
# limits of the tiny float32 cell: the program agrees with the reference to
# about 1e-6 on the CPU, and the control reads above 1e-2
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}


def benchmark_cell():
    """The first cell of BENCHMARK.json."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"][0]


def cell_config():
    """The first cell's configuration, as it is run on the chip."""
    name = benchmark_cell()["config"]
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def tiny_config():
    """The first cell's configuration at 2 layers, width 256, float32."""
    cfg = cell_config()
    cfg.update(TINY_MODEL)
    cfg["ssm"] = dict(cfg["ssm"], head_dim=32, state_dim=16)
    return cfg


def tiny_traffic(k):
    name = benchmark_cell()["traffic"]
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        tr = json.load(f)
    tr.update(TINY_TRAFFIC, k_perturbations=k, fused_contraction=k > 1)
    return tr


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-like root: BENCHMARK.json with the tiny cells, and bench/
    with their configuration, traffic and limits files added."""
    root = str(tmp_path_factory.mktemp("root"))
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["workloads"] = []
    for k in (1, 4):
        name = f"tiny.k{k}"
        write(os.path.join(root, "bench", "traffic", f"k{k}.json"),
              tiny_traffic(k))
        write(os.path.join(root, "bench", "limits", f"{name}.json"),
              TINY_LIMITS)
        benchmark["workloads"].append({"name": name, "config": "tiny",
                                       "traffic": f"k{k}", "chips": 1,
                                       "why": "CPU test cell"})
    write(os.path.join(root, "bench", "configs", "tiny.json"), tiny_config())
    write(os.path.join(root, "BENCHMARK.json"), benchmark)
    return root
