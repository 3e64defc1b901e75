"""Share of its roofline for one kernel family, from the trace of the
window: for every device event of the family's tpu_custom_calls, the least
time the chip could take (the larger of the required FLOPs over the peak
FLOP/s and the required bytes over the HBM bandwidth, from
``bench/counts/<entry point>.py``), summed, over the events' summed device
time. None where the window ran no call of the family."""
from __future__ import annotations


def family_share(run, family):
    cell, trace, peaks = run["cell"], run["trace"], run["peaks"]
    events = [(instr, entry, s) for instr, entry, s in trace.kernel_events
              if entry.startswith(family)]
    if not events:
        return None
    least, spent = 0.0, 0.0
    counts = {}
    for instr, entry, seconds in events:
        if entry not in counts:
            counts[entry] = cell.counts(entry)
        flops, nbytes = counts[entry].call(
            cell.cfg, cell.traffic, {"operands": trace.sites[instr]})
        least += max(flops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
        spent += seconds
    return 100.0 * least / spent
