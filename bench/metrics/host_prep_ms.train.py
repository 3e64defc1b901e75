"""Host time per round to sample the clients, stack their batches and put
them on the device: the benchmark's 'prep' spans over the window."""


def read(run):
    w = run["window"]
    return 1000.0 * w.prep_s / w.rounds
