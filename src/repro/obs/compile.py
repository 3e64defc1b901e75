"""Compile counter: the time the process spent turning jitted functions into
executables, from JAX's own monitoring events.

    trace_s     /jax/core/compile/jaxpr_trace_duration
    lower_s     /jax/core/compile/jaxpr_to_mlir_module_duration
    backend_s   /jax/core/compile/backend_compile_duration (an XLA compile,
                or a load from the persistent compilation cache)
    seconds     wall time inside any of the three (a jit traced inside
                another's trace is counted once)
    compiles    backend compiles and cache loads
    cache_hits, cache_misses   /jax/compilation_cache/cache_{hits,misses}

The listeners are registered once, when ``repro.obs`` is imported; what
compiled before that is not counted. ``compile_stats()`` returns the
totals so far. Listening is host-side and reads only what JAX reports: it
never changes a traced program.
"""
from __future__ import annotations

import threading

import jax

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_lock = threading.Lock()
_totals = dict.fromkeys(("compiles", "cache_hits", "cache_misses"), 0)
_totals.update(dict.fromkeys(_DURATIONS.values(), 0.0))
_spans: list = []           # (start, end) wall times, merged on read
_registered = False


def _on_span(event, start, end, **kwargs):
    key = _DURATIONS.get(event)
    if key is None:
        return
    with _lock:
        _totals[key] += end - start
        if key == "backend_s":
            _totals["compiles"] += 1
        _spans.append((start, end))


def _on_event(event, **kwargs):
    key = _COUNTS.get(event)
    if key is not None:
        with _lock:
            _totals[key] += 1


def _merged():
    """Merge ``_spans`` in place; returns their union's length."""
    merged = []
    for s, e in sorted(_spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    _spans[:] = [tuple(m) for m in merged]
    return sum(e - s for s, e in merged)


def register():
    """Start listening (idempotent)."""
    global _registered
    with _lock:
        if _registered:
            return
        _registered = True
    jax.monitoring.register_event_time_span_listener(_on_span)
    jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    """Totals since ``repro.obs`` was imported (see the module docstring)."""
    with _lock:
        out = dict(_totals)
        out["seconds"] = _merged()
    return out
