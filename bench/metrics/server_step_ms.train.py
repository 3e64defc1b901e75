"""Device time per round of the server's share: the operations under the
program's ``spry.server`` scope and no client scope (assignment,
aggregation, FedYogi, the round's metrics; ``bench/phases.py``)."""
from phases import phase_ms


def read(run):
    return phase_ms(run, "server")
