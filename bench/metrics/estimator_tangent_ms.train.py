"""Device time per round of the K tangent passes: the operations under the
program's ``spry.tangents`` scope (``bench/phases.py``)."""
from phases import phase_ms


def read(run):
    return phase_ms(run, "tangents")
