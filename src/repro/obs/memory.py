"""Memory probes: runtime residency sampled at probe points.

``jax.live_arrays()`` totals and per-device ``memory_stats()``, recorded in
a ``memory`` telemetry event. A device's peak is ``peak_bytes_in_use +
peak_bytes_reserved``: on a TPU the compiled programs' temporaries are
reserved apart and never reach ``peak_bytes_in_use``, so the in-use peak
alone misses what sets a training step's peak (the benchmark's
``peak_hbm_gib`` is the same sum).

Probing is host-side and read-only: sampling allocates nothing on device
and never touches a traced program.
"""
from __future__ import annotations

from typing import Dict


def live_array_bytes() -> int:
    """Total bytes of every live device array in the process."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            total += int(a.nbytes)
        except Exception:       # deleted/donated arrays can race the walk
            continue
    return total


def device_memory_stats() -> Dict[str, Dict]:
    """Per-device allocator stats where the backend exposes them (TPU/GPU;
    the CPU backend returns none — the live-array total still applies)."""
    import jax

    out = {}
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[f"{dev.platform}:{dev.id}"] = {
                k: int(v) for k, v in stats.items()
                if isinstance(v, (int, float))}
    return out


class MemoryProbe:
    """Samples runtime residency into gauges + ``memory`` events.

    ``sample(label)`` records live-array bytes and, where the backend keeps
    statistics, each device's stats, the bytes in use over all devices and
    each device's peak in use plus reserved (``device_peak_bytes``).
    """

    def __init__(self, telemetry):
        self.telemetry = telemetry
        self._g_live = telemetry.gauge("mem.live_array_bytes")
        self._g_peak = telemetry.gauge("mem.device_peak_bytes")

    def sample(self, label: str) -> Dict:
        rec = {"label": label, "live_bytes": live_array_bytes()}
        stats = device_memory_stats()
        if stats:
            rec["device_stats"] = stats
            rec["device_bytes_in_use"] = sum(
                s.get("bytes_in_use", 0) for s in stats.values())
            peaks = {dev: s["peak_bytes_in_use"]
                     + s.get("peak_bytes_reserved", 0)
                     for dev, s in stats.items() if "peak_bytes_in_use" in s}
            if peaks:
                rec["device_peak_bytes"] = peaks
                self._g_peak.set(max(peaks.values()))
        self._g_live.set(rec["live_bytes"])
        self.telemetry.event("memory", **rec)
        return rec
