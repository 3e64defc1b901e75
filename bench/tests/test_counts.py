"""The counts from shapes: the round step's count is the sum of its
per-client, per-layer counts; a tiny unrolled forward's matrix products, as
XLA itself counts them, equal the counted ones; and XLA's cost_analysis of
the scanned forward undercounts, so it is never the source."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cell import load_module
from conftest import BENCH, cell_config, tiny_config, tiny_traffic

step = load_module(os.path.join(BENCH, "counts", "step.rwkv6.py"),
                   "bench_counts_step.rwkv6")
ref = load_module(os.path.join(BENCH, "reference", "rwkv6.py"),
                  "bench_reference_rwkv6")


@pytest.mark.parametrize("k", [1, 8])
def test_round_count_is_sum_of_layers(k):
    cfg = cell_config()
    tr = tiny_traffic(k)
    primal, tangent = step.layer_flops(cfg)
    head = 2 * cfg["d_model"] * cfg["n_classes"] * tr["batch_size"]
    tokens = tr["batch_size"] * tr["seq_len"]
    for r in range(tr["clients_per_round"] + 1):
        firsts = step.first_perturbed_layer(cfg, tr, r)
        want = 0
        for first in firsts:
            for layer in range(cfg["n_layers"]):
                want += tokens * primal
                want += k * tokens * tangent * (layer >= first)
            want += head * (1 + k)
        assert step.round_flops(cfg, tr, r) == want


def _dot_flops(hlo_text):
    """2 x output elements x contracted size, summed over XLA's dot ops."""
    import re
    define = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[([\d,]*)\]")
    dims = {}
    for line in hlo_text.splitlines():
        m = define.match(line)
        if m:
            dims[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    total = 0
    for line in hlo_text.splitlines():
        m = define.match(line)
        if not m or " dot(" not in line:
            continue
        lhs = re.search(r" dot\(%([\w.\-]+)", line).group(1)
        contract = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
        k = np.prod([dims[lhs][int(i)] for i in contract.group(1).split(",")])
        total += 2 * int(np.prod(dims[m.group(1)])) * int(k)
    return total


def _tiny_inputs():
    cfg = tiny_config()
    # rank 2: XLA writes a rank-1 product as a broadcast multiply, not a dot
    cfg.update(n_layers=4, lora_rank=2)
    key = jax.random.PRNGKey(0)
    base, peft = ref.init_base(cfg, key), ref.init_peft(cfg, key)
    B, S = 2, 16
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B,), jnp.int32)}
    return cfg, base, peft, batch, B * S


def _unrolled_matmuls(cfg, base, peft, batch):
    """The reference's layers in a Python loop, without the recurrence."""
    def f(base, peft, batch):
        h = jnp.take(base["embed"], batch["tokens"], axis=0)
        for layer in range(cfg["n_layers"]):
            lp = jax.tree.map(lambda t: t[layer], base["layers"])
            pl = jax.tree.map(lambda t: t[layer], peft["layers"])
            h = ref._layer(cfg, ref.plain_mm, 1.0, h, lp, pl)
        return h
    return f


def test_unrolled_matmuls_match_xla(monkeypatch):
    cfg, base, peft, batch, tokens = _tiny_inputs()
    # the recurrence's einsum sits in a scan; count the products around it
    monkeypatch.setattr(ref, "_wkv", lambda r, k, v, w, u: r * k * v * w)
    f = _unrolled_matmuls(cfg, base, peft, batch)
    hlo = jax.jit(f).lower(base, peft, batch).compile().as_text()
    counted = tokens * cfg["n_layers"] * step.layer_matmul_flops(cfg)
    assert _dot_flops(hlo) == counted


def test_cost_analysis_undercounts_scanned_step():
    cfg, base, peft, batch, tokens = _tiny_inputs()
    compiled = jax.jit(lambda b, p, x: ref.loss(cfg, b, p, x)).lower(
        base, peft, batch).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    primal, _ = step.layer_flops(cfg)
    required = tokens * cfg["n_layers"] * primal
    # the layer scan's body is counted once, not once per layer
    assert analysis["flops"] < 0.5 * required
