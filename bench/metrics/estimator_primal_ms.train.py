"""Device time per round of the estimator's once-per-estimate work: the
operations under the program's ``spry.primal`` scope (the linearize, the
site primal, the post-head cotangents; ``bench/phases.py``)."""
from phases import phase_ms


def read(run):
    return phase_ms(run, "primal")
