"""One call of ``lora_dual_mt_tangents``: the K tangents of a LoRA-adapted
projection y = x W + s (x A) B over every row of the round,

    yd_t = xd_t W + s ((xd_t A + x ad_t) B + (x A) bd_t)

with x, xd_t and yd_t in the activation dtype, the trainable factors and
their tangents in float32, W read once. ``site`` gives the call's operands
as compiled: W is the one 2-D operand in the activation dtype, and a call
without an input tangent has 6 operands instead of 7."""
from __future__ import annotations

from sizes import itemsize, tokens_per_round


def call(cfg, traffic, site):
    act = itemsize(cfg)
    w = [s for dt, s in site["operands"] if len(s) == 2 and
         dt == {2: "bf16", 4: "f32"}[act]]
    din, dout = max(w, key=lambda s: s[0] * s[1])
    has_xd = len(site["operands"]) == 7
    r = cfg["lora_rank"]
    T = traffic["k_perturbations"]
    M = traffic["clients_per_round"]
    _, N = tokens_per_round(traffic)
    flops = 2 * N * din * r + T * (
        2 * N * din * r + 2 * N * r * dout * 2
        + (2 * N * din * dout + 2 * N * din * r if has_xd else 0))
    nbytes = (N * din * act + din * dout * act
              + (din + dout) * r * 4 * (1 + M * T)
              + T * N * dout * act
              + (T * N * din * act if has_xd else 0))
    return flops, nbytes
