"""Shared sizes for the counts: what one call of a kernel entry point, or
one round step, requires by the algorithm, from the configuration and the
traffic. Padding, recomputation and zero tangents are not counted."""
from __future__ import annotations

import numpy as np


def itemsize(cfg):
    return np.dtype(cfg["param_dtype"]).itemsize


def tokens_per_round(traffic):
    """(sequences, tokens) of all clients in one round."""
    seqs = traffic["clients_per_round"] * traffic["batch_size"]
    return seqs, seqs * traffic["seq_len"]
