"""The harness finds configurations, traffic mixes, per-layer metrics and
counts by file name: a configuration, a traffic mix and a metric dropped in
as new files, with a new cell in BENCHMARK.json, are picked up and run with
no edit to an existing file under bench/."""
import hashlib
import json
import os
import shutil

import cell as cells
import run as bench
from conftest import TINY_LIMITS, tiny_config, tiny_traffic, write


def digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in base:
                continue
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_picked_up(tiny_root, tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(tiny_root, "bench"),
                    os.path.join(root, "bench"))
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    before = digest(os.path.join(root, "bench"))

    bench_dir = os.path.join(root, "bench")
    cfg = tiny_config()
    cfg["n_layers"] = 3
    write(os.path.join(bench_dir, "configs", "newcfg.json"), cfg)
    traffic = tiny_traffic(2)
    traffic["clients_per_round"] = 2
    write(os.path.join(bench_dir, "traffic", "newmix.json"), traffic)
    write(os.path.join(bench_dir, "limits", "newcfg.newmix.json"),
          TINY_LIMITS)
    with open(os.path.join(bench_dir, "metrics", "rounds_seen.py"), "w") as f:
        f.write("def read(run):\n    return float(run['window'].rounds)\n")
    benchmark["workloads"].append({"name": "newcfg.newmix",
                                   "config": "newcfg", "traffic": "newmix",
                                   "chips": 1, "why": "dropped in"})
    benchmark["per_layer"].append({
        "name": "rounds_seen", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "entry loop",
        "moves": "train_tokens_per_s", "workloads": ["newcfg.newmix"]})
    write(os.path.join(root, "BENCHMARK.json"), benchmark)

    after = digest(bench_dir)
    assert all(after[k] == v for k, v in before.items())

    c = cells.Cell(root, "newcfg.newmix", bench_dir)
    assert c.cfg["n_layers"] == 3 and c.traffic["clients_per_round"] == 2
    assert [m["name"] for m in c.metrics("per_layer")][-1] == "rounds_seen"
    result, _ = bench.run(root, "newcfg.newmix", 5, 0.3, False,
                          require_chip=False, bench_dir=bench_dir)
    assert result["correct"]
    w = bench.Window(rounds=7)
    assert c.reader("rounds_seen").read({"window": w}) == 7.0
