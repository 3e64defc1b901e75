"""One call of ``wkv6_scan``: the WKV6 recurrence over every sequence of
the round from a zero state, in float32. Per token and head of size hd:
S += k v^T after decay (3 hd^2), y = r^T (S + u k v^T) (4 hd^2)."""
from __future__ import annotations

from sizes import tokens_per_round

RECURRENCE = 7


def call(cfg, traffic, site):
    d, hd = cfg["d_model"], cfg["ssm"]["head_dim"]
    _, N = tokens_per_round(traffic)
    flops = RECURRENCE * hd * d * N
    nbytes = 4 * (4 * N * d + d + N * d)
    return flops, nbytes
