"""One call of ``wkv6_scan_mt_jvps``: the K tangents of the WKV6
recurrence contracted against the output cotangent gy, <gy, yd_t>; the
tangent outputs never leave the kernel."""
from __future__ import annotations

from sizes import tokens_per_round
from wkv6_scan import RECURRENCE


def call(cfg, traffic, site):
    d, hd = cfg["d_model"], cfg["ssm"]["head_dim"]
    T = traffic["k_perturbations"]
    _, N = tokens_per_round(traffic)
    flops = T * (2 * RECURRENCE * hd * d * N + 2 * N * d)
    nbytes = 4 * (4 * N * d + d + T * 4 * N * d + N * d + T)
    return flops, nbytes
