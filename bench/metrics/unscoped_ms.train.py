"""Device time per round under no ``spry.`` phase scope: the layout copies
XLA adds after lowering, and whatever else escapes the program's phases
(``bench/phases.py``). With the four phase metrics it sums to the busy time
per round."""
from phases import UNSCOPED, phase_ms


def read(run):
    return phase_ms(run, UNSCOPED)
