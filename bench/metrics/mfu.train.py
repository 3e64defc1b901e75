"""Model FLOP/s utilization of the round step: the FLOPs the window's rounds
require (``bench/counts/step.<reference>.py``) over the window's seconds,
as a share of the chip's peak bf16 FLOP/s."""


def read(run):
    cell, w = run["cell"], run["window"]
    counts = cell.counts(f"step.{cell.cfg['reference']}")
    flops = sum(counts.round_flops(cell.cfg, cell.traffic, r)
                for r in range(w.first_round, w.first_round + w.rounds))
    return 100.0 * flops / w.seconds / run["peaks"]["bf16_flops"]
