"""Device time per round of the clients' own work: the operations under the
program's ``spry.client_update`` scope and no estimator scope
(perturbations, the jvp-v combine, the local SGD step; ``bench/phases.py``)."""
from phases import phase_ms


def read(run):
    return phase_ms(run, "client_update")
