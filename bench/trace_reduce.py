"""From a profiler trace of the measured window to the benchmark's device
numbers.

    busy_s       union of the intervals in which an operation ran on a
                 device ("XLA Ops" line of each TPU plane), inside the
                 window, averaged over the devices
    window_s     first 'prep' span start to last 'loss_read' span end (the
                 benchmark's own host spans, on the profiler's clock)
    kernel_s     device seconds per kernel entry point: the events of the
                 tpu_custom_call instructions of the compiled step
    device_ops   device seconds per operation name (custom calls named by
                 their entry point)
    idle_gaps    each stretch inside the window in which no device operation
                 ran, labelled by the host span that overlaps it most
                 ('prep', 'dispatch', 'loss_read', or 'other')
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from hlo import kernel_site_operands, kernel_sites

HOST_SPANS = ("prep", "dispatch", "loss_read")
OPS_LINE = "XLA Ops"
# a device event is named by its HLO instruction: "%name = type opcode(...)"
_EVENT = re.compile(r"^%?([\w.\-]+)\s*=\s*(.*)$", re.S)
# control-flow ops span the operations they run; they are not work
_CONTAINER = re.compile(r"(while|conditional|call)\(")


def instruction(event_name):
    """(instruction name, is a control-flow container) of a device event."""
    m = _EVENT.match(event_name)
    if not m:
        return event_name, False
    return m.group(1), bool(_CONTAINER.match(_opcode_part(m.group(2))))


def _opcode_part(rest):
    """The text from the opcode on: skip the result type, which may be a
    tuple in parentheses."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return rest[i + 1:].lstrip()
    return rest.split(" ", 1)[1] if " " in rest else rest


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    device_ops: dict
    kernel_s: dict
    kernel_events: list        # (instruction, entry, seconds)
    idle_gaps: list            # (label, seconds)
    sites: dict                # instruction -> operands

    def breakdown(self):
        ops = sorted(self.device_ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes, hlo_text):
    """``planes``: iterable of (plane name, [(line name, [(name, start_ns,
    duration_ns)])]), as ``jax.profiler.ProfileData`` gives them."""
    sites = kernel_sites(hlo_text)
    host, devices = [], []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            for lname, events in lines:
                if lname == OPS_LINE:
                    ops = []
                    for n, st, d in events:
                        name, container = instruction(n)
                        if not container:
                            ops.append((name, st, d))
                    devices.append(ops)
        elif pname.startswith("/host:"):
            for _, events in lines:
                host += [(n, s, s + d) for n, s, d in events
                         if n in HOST_SPANS]
    if not devices:
        raise ValueError("the trace holds no TPU operations")
    preps = [s for n, s, _ in host if n == "prep"]
    reads = [e for n, _, e in host if n == "loss_read"]
    if not preps or not reads:
        raise ValueError("the trace holds none of the benchmark's host spans")
    lo, hi = min(preps), max(reads)

    busy, device_ops, kernel_s, kernel_events = 0.0, {}, {}, []
    merged_first = None
    for events in devices:
        inside = [(n, s, d) for n, s, d in events if s < hi and s + d > lo]
        merged = _union(_clip([(s, s + d) for _, s, d in inside], lo, hi))
        merged_first = merged_first if merged_first is not None else merged
        busy += sum(e - s for s, e in merged) * 1e-9
        for n, s, d in inside:
            entry = sites.get(n)
            key = f"{entry} ({n})" if entry else n
            device_ops[key] = device_ops.get(key, 0.0) + d * 1e-9
            if entry:
                kernel_s[entry] = kernel_s.get(entry, 0.0) + d * 1e-9
                kernel_events.append((n, entry, d * 1e-9))
    n_dev = len(devices)
    device_ops = {k: v / n_dev for k, v in device_ops.items()}
    kernel_s = {k: v / n_dev for k, v in kernel_s.items()}

    gaps, prev = [], lo
    for s, e in merged_first + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    idle = []
    for gs, ge in gaps:
        best, label = 0, "other"
        for n, hs, he in host:
            overlap = min(ge, he) - max(gs, hs)
            if overlap > best:
                best, label = overlap, n
        idle.append((label, (ge - gs) * 1e-9))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy / n_dev,
                   device_ops=device_ops, kernel_s=kernel_s,
                   kernel_events=kernel_events, idle_gaps=idle,
                   sites=kernel_site_operands(hlo_text))


def planes_of(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        yield plane.name, [
            (line.name, [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events])
            for line in plane.lines]


def reduce_file(path, hlo_text):
    return reduce_planes(planes_of(path), hlo_text)


def reduce_dir(trace_dir, hlo_text):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {files}")
    return reduce_file(files[0], hlo_text)
