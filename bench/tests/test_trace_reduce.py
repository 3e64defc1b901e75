"""The reduction from a profiler trace to the device numbers, on a small
trace recorded on a TPU v5e (a half-second window of the rwkv6 K=8 fused
cell at 4 layers, kept under bench/tests/data/) and on hand-made planes whose answer is known."""
import gzip
import os

import pytest

import trace_reduce
from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data")
HLO = ('  %k.1 = f32[8]{0} custom-call(%a), custom_call_target='
       '"tpu_custom_call", operand_layout_constraints={f32[8]{0}}, '
       'metadata={op_name="jit(step)/jit(lora_dual_mt_tangents)/pallas_call"}')


def ms(x):
    return int(x * 1e6)


def test_hand_made_planes():
    device = [
        ("%while.1 = (s32[]) while((s32[]) %t), condition=%c", ms(0), ms(9)),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x)", ms(1), ms(2)),
        ("%k.1 = f32[8]{0} custom-call(f32[8]{0} %a)", ms(3), ms(4)),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x)", ms(8), ms(1)),
    ]
    host = [("prep", ms(0), ms(1)), ("dispatch", ms(1), ms(1)),
            ("loss_read", ms(2), ms(8)), ("other", ms(0), ms(10))]
    planes = [("/device:TPU:0", [("XLA Ops", device), ("XLA Modules", [])]),
              ("/host:CPU", [("python3", host)])]
    r = trace_reduce.reduce_planes(planes, HLO)
    assert r.window_s == pytest.approx(0.010)
    # busy [1, 3) + [3, 7) + [8, 9): the while loop is not work
    assert r.busy_s == pytest.approx(0.007)
    assert r.kernel_s == {"lora_dual_mt_tangents": pytest.approx(0.004)}
    assert sorted(r.idle_gaps) == [("loss_read", pytest.approx(0.001)),
                                   ("loss_read", pytest.approx(0.001)),
                                   ("prep", pytest.approx(0.001))]
    top = r.breakdown()["device_ops"][0]
    assert top[0] == "lora_dual_mt_tangents (k.1)"


def test_recorded_trace(tmp_path):
    with gzip.open(os.path.join(DATA, "step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    trace = tmp_path / "window.xplane.pb"
    with gzip.open(os.path.join(DATA, "window.xplane.pb.gz"), "rb") as f:
        trace.write_bytes(f.read())
    r = trace_reduce.reduce_file(str(trace), hlo)
    assert 0 < r.busy_s <= r.window_s
    assert set(r.kernel_s) == {"lora_dual_mt_tangents", "wkv6_scan",
                               "wkv6_scan_mt_tangents", "wkv6_scan_mt_jvps"}
    assert sum(r.kernel_s.values()) < r.busy_s
    assert all(label in trace_reduce.HOST_SPANS + ("other",)
               for label, _ in r.idle_gaps)
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
