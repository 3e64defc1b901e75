"""A cell of the benchmark, found by name: its entry in ``BENCHMARK.json``,
its configuration file, its traffic file, the plain reference the
configuration names, its limits, and the readers of its per-layer metrics.
Each of these is a file of its own under ``bench/``; adding a cell, a
configuration, a traffic mix or a metric adds files and edits none."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise SystemExit(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, root, workload, bench_dir=BENCH_DIR):
        self.root, self.bench_dir = root, bench_dir
        self.benchmark = read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.config_name = self.workload["config"]
        self.cfg = read_json(self._path("configs", self.config_name, ".json"))
        self.traffic = read_json(self._path("traffic",
                                            self.workload["traffic"], ".json"))
        self.reference = load_module(
            self._path("reference", self.cfg["reference"], ".py"),
            f"bench_reference_{self.cfg['reference']}")

    def _path(self, kind, name, ext):
        return os.path.join(self.bench_dir, kind, name + ext)

    def metrics(self, kind):
        """The cell's ``end_to_end`` or ``per_layer`` entries."""
        return [m for m in self.benchmark[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric):
        return load_module(self._path("metrics", metric, ".py"),
                           f"bench_metric_{metric}")

    def counts(self, name):
        counts_dir = os.path.join(self.bench_dir, "counts")
        if counts_dir not in sys.path:
            sys.path.insert(0, counts_dir)
        return load_module(self._path("counts", name, ".py"),
                           f"bench_counts_{name}")
