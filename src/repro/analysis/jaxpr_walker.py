"""Shared jaxpr-walking pass: every eqn, every nesting level, one place.

This generalizes the ad-hoc ``_walk_eqns`` / ``_pallas_calls`` /
``_assert_no_tangent_stack_output`` helpers that used to be copy-pasted
across ``tests/test_jvps_epilogue.py`` / ``test_split_forward.py`` /
``test_mt_mixers.py`` into the one pass the static-analysis rules and all
tests call. Sub-jaxprs are found wherever primitives carry them: scan /
while / pjit / custom_jvp bodies hold a single (Closed)Jaxpr param,
``cond`` holds a tuple of branches, and ``pallas_call`` carries the kernel
body itself (whose invars are the VMEM block/scratch refs the vmem model
reads).
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np


def _inner_jaxprs(param):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    if isinstance(param, (tuple, list)):
        for p in param:
            yield from _inner_jaxprs(p)
        return
    inner = getattr(param, "jaxpr", None)
    if inner is not None:
        yield inner if hasattr(inner, "eqns") else inner.jaxpr


def walk_eqns(jaxpr) -> Iterator:
    """Yield every eqn of ``jaxpr`` (Jaxpr or ClosedJaxpr), recursing into
    sub-jaxprs carried in eqn params (scan/while/cond/pjit bodies,
    custom_jvp/vjp closures, pallas_call kernel bodies)."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in j.eqns:
        yield eqn
        for p in eqn.params.values():
            for inner in _inner_jaxprs(p):
                yield from walk_eqns(inner)


def pallas_calls(jaxpr) -> List:
    """All ``pallas_call`` eqns anywhere in a (nested) jaxpr."""
    return [e for e in walk_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def kernel_src(eqn) -> str:
    """The kernel body's source info, e.g.
    ``'_mt_jvps_kernel at .../kernels/lora_dual/kernel.py:161'``."""
    return str(eqn.params["jaxpr"].debug_info.func_src_info)


def kernel_name(eqn) -> str:
    """Just the kernel function name (``'_mt_jvps_kernel'``)."""
    return kernel_src(eqn).split(" at ")[0].strip()


def family_pallas_calls(jaxpr, family: str) -> List:
    """pallas_calls whose source path mentions ``family`` (e.g.
    ``'lora_dual'`` / ``'wkv6_scan'`` / ``'swa_attention'`` /
    ``'mamba2_scan'``) — upstream (non-site) mixers legitimately
    materialize their tangents, so site checks filter by kernel family."""
    return [e for e in pallas_calls(jaxpr) if family in kernel_src(e)]


def tangent_stack_size(K: int, y_shape) -> int:
    """Element count of the (K,) + y_shape tangent stack the contraction
    epilogues exist to remove."""
    return int(K) * int(np.prod(y_shape))


def tangent_stack_outputs(jaxpr, K: int, y_shape,
                          family: str = None) -> List[Tuple]:
    """Every (eqn, outvar) where a pallas_call WRITES a buffer at least as
    large as the (K,) + y_shape tangent stack. Site INPUT tangents of that
    size are unavoidable (they are kernel operands); the invariant targets
    kernel outputs — the buffers the ``*_mt_tangents`` route materializes
    and the ``*_mt_jvps`` epilogues replace with per-block partials."""
    stack = tangent_stack_size(K, y_shape)
    calls = (family_pallas_calls(jaxpr, family) if family
             else pallas_calls(jaxpr))
    return [(eqn, var) for eqn in calls for var in eqn.outvars
            if var.aval.size >= stack]


def assert_no_tangent_stack(jaxpr, K: int, y_shape, family: str = None):
    """Raise AssertionError if any pallas_call writes a tangent-stack-sized
    buffer — the drop-in replacement for the old per-test helpers."""
    for eqn, var in tangent_stack_outputs(jaxpr, K, y_shape, family=family):
        raise AssertionError(
            f"kernel writes a tangent-stack-sized buffer {var.aval.shape} "
            f"(>= K x y = {tangent_stack_size(K, y_shape)} elems): {eqn}")
