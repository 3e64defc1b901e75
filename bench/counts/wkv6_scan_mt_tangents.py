"""One call of ``wkv6_scan_mt_tangents``: the K tangents of the WKV6
recurrence over every sequence of the round (product rule: twice the
primal's work per tangent), reading the primal operands once. The primal
state walk the kernel repeats is recomputation and is not counted."""
from __future__ import annotations

from sizes import tokens_per_round
from wkv6_scan import RECURRENCE


def call(cfg, traffic, site):
    d, hd = cfg["d_model"], cfg["ssm"]["head_dim"]
    T = traffic["k_perturbations"]
    _, N = tokens_per_round(traffic)
    flops = T * 2 * RECURRENCE * hd * d * N
    nbytes = 4 * (4 * N * d + d + T * 4 * N * d + T * N * d)
    return flops, nbytes
