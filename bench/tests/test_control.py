"""The control, the plain reference computed with float8 matrix products in
the program's place, comes out not correct at the tiny size; so do the
planted faults read against the reference (the same readings that
``bench/readings.py`` takes on the chip at the cells' own sizes)."""
import os

import pytest

import correct
import readings
import run as bench
from cell import Cell
from conftest import TINY_LIMITS


@pytest.mark.parametrize("workload", ["tiny.k1", "tiny.k4"])
def test_control_and_faults_fail(tiny_root, workload):
    cell = Cell(tiny_root, workload, os.path.join(tiny_root, "bench"))
    seed = 2 ** 31 + 7
    batches, prog = readings.program_first_rounds(cell, seed)
    ref = bench.reference_rounds(cell, seed, batches)
    ok, _ = correct.judge(correct.numbers(prog, ref), TINY_LIMITS)
    assert ok
    ctl = bench.reference_rounds(cell, seed, batches, mm=correct.fp8_mm)
    half = bench.reference_rounds(
        cell, seed, batches,
        alter=lambda b: readings.faults.half_batch(*b))
    for bad in (ctl, half, readings.altered(ref)):
        ok, checks = correct.judge(correct.numbers(bad, ref), TINY_LIMITS)
        assert not ok, checks


def test_null_limit_is_not_compared(tmp_path):
    (tmp_path / "limits").mkdir()
    (tmp_path / "limits" / "w.json").write_text(
        '{"loss_gap": null, "grad_gap": 0.5, "change_gap": 0.1}')
    limits = correct.load_limits(str(tmp_path), "w")
    assert limits == {"grad_gap": 0.5, "change_gap": 0.1}
    ok, checks = correct.judge(
        {"loss_gap": 9.0, "grad_gap": 0.4, "change_gap": 0.05}, limits)
    assert ok and set(checks) == {"grad_gap", "change_gap"}
    ok, _ = correct.judge(
        {"loss_gap": 0.0, "grad_gap": 0.4, "change_gap": float("nan")},
        limits)
    assert not ok
