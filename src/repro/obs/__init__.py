"""Unified telemetry: metrics registry, span tracing, sinks, memory
probes, the compile counter, and the run-artifact report CLI
(``python -m repro.obs.report``).

Everything records host-side on already-returned values: telemetry-on is
bit-identical to telemetry-off on every traced program; telemetry-off
(``NULL``) is a preallocated no-op object. See ``obs/telemetry.py``.
Importing this package starts the compile counter (``obs/compile.py``).
"""
from repro.obs import compile as _compile
from repro.obs.compile import compile_stats
from repro.obs.memory import (
    MemoryProbe,
    device_memory_stats,
    live_array_bytes,
)
from repro.obs.metrics import (
    DEFAULT_BYTES_BUCKETS,
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.sinks import (
    InMemorySink,
    JSONLSink,
    PrometheusTextfileSink,
    Sink,
)
from repro.obs.telemetry import NULL, NullTelemetry, Telemetry, make_telemetry
from repro.obs.trace import (
    SpanRecord,
    Tracer,
    chrome_trace_doc,
    load_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "NULL", "NullTelemetry", "Telemetry", "make_telemetry",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS", "DEFAULT_BYTES_BUCKETS",
    "Sink", "JSONLSink", "InMemorySink", "PrometheusTextfileSink",
    "SpanRecord", "Tracer", "chrome_trace_doc", "write_chrome_trace",
    "load_chrome_trace",
    "MemoryProbe", "live_array_bytes", "device_memory_stats",
    "compile_stats",
]

_compile.register()
