"""Device time per phase of the Spry round, from the trace of the window and
the compiled step's HLO.

The program names the phases of its round step with ``jax.named_scope``
(``src/repro/core/spry.py``, ``core/forward_grad.py``, ``fl/server.py``),
so each HLO instruction's ``op_name`` holds one or more of
``spry.primal``, ``spry.tangents``, ``spry.client_update`` and
``spry.server``. Scopes nest, and the last one names the phase, with one
exception: an op under ``spry.tangents`` is tangent work whatever follows.
The linear map that ``jax.linearize`` returns replays, inside the scope in
which it is applied, the name stack it was built under, so its ops read
``.../spry.tangents/vmap(spry.primal)/...``.

A device event is named by its instruction (``bench/trace_reduce.py``
keys a kernel's events ``"<entry point> (<instruction>)"``). An instruction
with no ``op_name``, such as a fusion whose root XLA made, takes the phase
of the ``op_name``s in the computation it calls. What has none is
'unscoped'. A program that names no phase (one built before the scopes)
gives no reading.
"""
from __future__ import annotations

import re

from hlo import _INSTR, _OP_NAME

PHASES = ("primal", "tangents", "client_update", "server")
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"spry\.(primal|tangents|client_update|server)\b")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_KERNEL_KEY = re.compile(r"^\w+ \(([\w.\-]+)\)$")


def phase_of(op_name):
    """The phase an ``op_name`` names, or None where no phase scope is open."""
    scopes = _SCOPE.findall(op_name)
    if not scopes:
        return None
    return "tangents" if "tangents" in scopes else scopes[-1]


def instruction_phases(hlo_text):
    """{instruction name: phase or None} over the HLO text."""
    own, calls, body = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and "=" not in line.split("{", 1)[0]:
            computation = head.group(1)
            body[computation] = []
            continue
        instr = _INSTR.match(line)
        if not instr:
            continue
        op = _OP_NAME.search(line)
        name = instr.group(1)
        own[name] = phase_of(op.group(1)) if op else None
        if computation is not None:
            body[computation].append(name)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    phases = {}
    for name, phase in own.items():
        if phase is None and name in calls:
            inner = [own[i] for i in body.get(calls[name], ()) if own.get(i)]
            phase = inner[-1] if inner else None
        phases[name] = phase
    return phases


def instruction_of(key):
    """The HLO instruction of a ``device_ops`` key."""
    kernel = _KERNEL_KEY.match(key)
    return kernel.group(1) if kernel else key


def phase_seconds(run):
    """{phase or 'unscoped': device seconds per round of the window}, or
    None where the compiled step names no phase."""
    phases = instruction_phases(run["program"].compiled.as_text())
    if not any(phases.values()):
        return None
    out = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    for key, seconds in run["trace"].device_ops.items():
        out[phases.get(instruction_of(key)) or UNSCOPED] += seconds
    rounds = run["window"].rounds
    return {k: v / rounds for k, v in out.items()}


def phase_ms(run, phase):
    """Device milliseconds per round spent in ``phase``, or None."""
    per_round = phase_seconds(run)
    return None if per_round is None else 1000.0 * per_round[phase]
