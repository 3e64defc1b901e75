"""FLOPs that one Spry round of an rwkv6 configuration requires: every
client's primal pass once, plus K tangent passes that start at the first
layer in which the client's assignment perturbs a unit (the layers before
carry zero tangents), plus the head. A layer's tangent costs what its
primal does in matrix products and twice the primal in the recurrence.
The layer in which a client's tangents start is counted whole."""
from __future__ import annotations

DECAY_RANK = 64
RECURRENCE = 7


def layer_matmul_flops(cfg):
    """FLOPs of one layer's matrix products per token, primal: the five
    time-mix projections, the decay's low-rank pair, the three channel-mix
    projections and the LoRA pairs."""
    d, F, r = cfg["d_model"], cfg["d_ff"], cfg["lora_rank"]
    return (2 * d * d * 5 + 2 * 2 * d * DECAY_RANK + 2 * d * d + 4 * d * F
            + len(cfg["lora_targets"]) * 2 * 2 * d * r)


def layer_flops(cfg):
    """(primal, tangent) FLOPs of one layer per token. A tangent repeats the
    frozen products, doubles the LoRA pairs' (input and factor tangents)
    and the recurrence's (product rule)."""
    d, hd, r = cfg["d_model"], cfg["ssm"]["head_dim"], cfg["lora_rank"]
    matmul = layer_matmul_flops(cfg)
    lora = len(cfg["lora_targets"]) * 2 * 2 * d * r
    rec = RECURRENCE * hd * d
    return matmul + rec, matmul + lora + 2 * rec


def first_perturbed_layer(cfg, traffic, round_idx):
    """Per client of the round, the first layer of the units it perturbs
    (units: per target in sorted order, per layer; client (i + round) % M
    takes unit i % U)."""
    L, M = cfg["n_layers"], traffic["clients_per_round"]
    U = L * len(cfg["lora_targets"])
    first = [L] * M
    for i in range(max(U, M)):
        c = (i + round_idx) % M
        first[c] = min(first[c], (i % U) % L)
    return first


def round_flops(cfg, traffic, round_idx):
    L, d, C = cfg["n_layers"], cfg["d_model"], cfg["n_classes"]
    K, B, S = (traffic["k_perturbations"], traffic["batch_size"],
               traffic["seq_len"])
    primal, tangent = layer_flops(cfg)
    head = 2 * d * C * B
    total = 0
    for first in first_perturbed_layer(cfg, traffic, round_idx):
        total += B * S * L * primal + head
        total += K * (B * S * (L - first) * tangent + head)
    return total
