"""Kernel calls in compiled HLO text. Each ``tpu_custom_call`` is named by
the innermost ``jit(<entry>)`` of its ``op_name``: the program's jitted
kernel entry points (``repro/kernels/*/ops.py``)."""
from __future__ import annotations

import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"jit\((\w+)\)")


def kernel_sites(hlo_text):
    """{HLO instruction name: kernel entry point} of every tpu_custom_call."""
    sites = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instr = _INSTR.match(line)
        op = _OP_NAME.search(line)
        names = _JIT.findall(op.group(1)) if op else []
        if instr:
            sites[instr.group(1)] = names[-1] if names else "?"
    return sites


_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_CONSTRAINTS = "operand_layout_constraints={"


def _operands(line):
    """[(dtype, shape)] of a custom call's operands, from its
    ``operand_layout_constraints`` (XLA prints the operands themselves
    without their types)."""
    start = line.find(_CONSTRAINTS)
    if start < 0:
        return []
    depth, i = 0, start + len(_CONSTRAINTS) - 1
    for j in range(i, len(line)):
        depth += {"{": 1, "}": -1}.get(line[j], 0)
        if depth == 0:
            break
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(line[i:j])]


def kernel_site_operands(hlo_text):
    """{HLO instruction name: [(dtype, shape)] of its operands} of every
    tpu_custom_call."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instr = _INSTR.match(line)
        if instr:
            out[instr.group(1)] = _operands(line)
    return out


def kernel_calls(hlo_text):
    """{kernel entry point: number of tpu_custom_call sites}."""
    counts = {}
    for entry in kernel_sites(hlo_text).values():
        counts[entry] = counts.get(entry, 0) + 1
    return counts
