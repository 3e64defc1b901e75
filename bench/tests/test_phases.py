"""Device time by phase of the Spry round (``bench/phases.py`` and the
readers of the phase metrics), on hand-made HLO whose answer is known and on
a small trace and HLO pair recorded on a TPU v5e (three rounds of the rwkv6
K=8 fused cell at 2 layers, B=1, kept under bench/tests/data/phases.*)."""
import gzip
import os
import types

import pytest

import cell as cells
import phases
import trace_reduce
from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data")
SCOPE = "jit(round_step)/spry.server/vmap(spry.client_update)/while/body"
HLO = f'''HloModule jit_round_step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %multiply.3 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{SCOPE}/jit(_normal)/mul"}}
}}

%bitcast_fusion (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %bitcast.1 = f32[8]{{0}} bitcast(%p)
}}

ENTRY %main.9 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %dot.1 = f32[8]{{0}} dot(%a, %a), metadata={{op_name="{SCOPE}/spry.primal/jvp()/dot_general"}}
  %k.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{SCOPE}/spry.tangents/vmap(spry.primal)/jvp(jit(lora_dual_mt_tangents))/pallas_call"}}
  %fusion.2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%bitcast_fusion
  ROOT %add.4 = f32[8]{{0}} add(%a, %a), metadata={{op_name="jit(round_step)/spry.server/add"}}
}}
'''
DEVICE_OPS = {"dot.1": 0.004, "lora_dual_mt_tangents (k.1)": 0.010,
              "fusion.2": 0.001, "fusion.3": 0.0005, "add.4": 0.0002}
# metric -> device ms per round of DEVICE_OPS over two rounds
EXPECTED = {"estimator_primal_ms.train": 2.0,
            "estimator_tangent_ms.train": 5.0,
            "client_update_ms.train": 0.5,
            "server_step_ms.train": 0.1,
            "unscoped_ms.train": 0.25}


def fake_run(hlo_text, device_ops, rounds):
    compiled = types.SimpleNamespace(as_text=lambda: hlo_text)
    return {"program": types.SimpleNamespace(compiled=compiled),
            "trace": types.SimpleNamespace(device_ops=device_ops),
            "window": types.SimpleNamespace(rounds=rounds)}


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                             f"test_metric_{name}")


def test_instruction_phases():
    of = phases.instruction_phases(HLO)
    # the linear map's ops are tangent work though spry.primal comes last;
    # a fusion with no op_name takes its body's phase, a bare bitcast none
    assert of == {"param_0": None, "multiply.3": "client_update",
                  "p": None, "bitcast.1": None, "a": None,
                  "dot.1": "primal", "k.1": "tangents",
                  "fusion.2": "client_update", "fusion.3": None,
                  "add.4": "server"}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_phase_reader(name):
    value = reader(name).read(fake_run(HLO, DEVICE_OPS, rounds=2))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_phase_reader_reads_nothing_without_scopes(name):
    """A program built before the scopes names no phase: no reading."""
    unscoped = HLO.replace("spry.", "scope.")
    assert reader(name).read(fake_run(unscoped, DEVICE_OPS, 2)) is None


def test_setup_compile_s_reads_the_compile_counter():
    import jax
    import numpy as np
    read = reader("setup_compile_s").read
    before = read({})      # the counter starts when repro.obs is imported
    jax.jit(lambda x: x - 5.0)(np.ones(3, np.float32))
    assert read({}) > before


def recorded():
    with gzip.open(os.path.join(DATA, "phases.step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    with gzip.open(os.path.join(DATA, "phases.window.xplane.pb.gz"),
                   "rb") as f:
        return hlo, f.read()


def test_recorded_phases_sum_to_the_busy_time(tmp_path):
    hlo, xplane = recorded()
    path = tmp_path / "window.xplane.pb"
    path.write_bytes(xplane)
    r = trace_reduce.reduce_file(str(path), hlo)
    rounds = sum(name == "loss_read"
                 for pname, lines in trace_reduce.planes_of(str(path))
                 if pname.startswith("/host:")
                 for _, events in lines for name, _, _ in events)
    assert rounds >= 2
    run = fake_run(hlo, r.device_ops, rounds)
    ms = {name: reader(name).read(run) for name in EXPECTED}
    busy_ms = 1000.0 * r.busy_s / rounds
    # device ops that cross the window's edges count whole in the phases
    assert sum(ms.values()) == pytest.approx(busy_ms, rel=0.01)
    assert ms["unscoped_ms.train"] <= 0.02 * busy_ms
    assert ms["estimator_tangent_ms.train"] > ms["estimator_primal_ms.train"]
    assert ms["estimator_primal_ms.train"] > ms["client_update_ms.train"]
    of = phases.instruction_phases(hlo)
    kernels = {(entry, of[instr]) for instr, entry, _ in r.kernel_events}
    assert kernels == {("wkv6_scan", "primal"),
                       ("wkv6_scan_mt_tangents", "tangents"),
                       ("wkv6_scan_mt_jvps", "tangents"),
                       ("lora_dual_mt_tangents", "tangents")}
