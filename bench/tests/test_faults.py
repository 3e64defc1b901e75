"""A whole run of the harness with its chip look skipped, at the tiny size:
sound, it comes out correct; with the timed path broken underneath, it
comes out not correct, for each fault a training cell on one chip can have
(there is no exchange between chips to leave out)."""
import os

import pytest

import faults
import run as bench


def tiny_run(root, workload, wrap_step=None):
    return bench.run(root, workload, 2 ** 31 + 101, 0.5, False,
                     require_chip=False, wrap_step=wrap_step,
                     bench_dir=os.path.join(root, "bench"))


@pytest.mark.parametrize("workload", ["tiny.k1", "tiny.k4"])
def test_sound_run_is_correct(tiny_root, workload):
    result, lines = tiny_run(tiny_root, workload)
    assert result["correct"], lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert lines[-3:] == [f"check {n}: {c['value']!r} limit {c['limit']!r}"
                          for n, c in result["checks"].items()]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", ["tiny.k1", "tiny.k4"])
def test_broken_step_is_not_correct(tiny_root, workload, fault):
    result, _ = tiny_run(tiny_root, workload, getattr(faults, fault))
    assert not result["correct"]
