"""Unit tests for the repro.obs telemetry subsystem: metrics registry,
span tracing + Chrome-trace export, sinks, the facade, the report CLI, the
spans' profiler annotations and the compile counter. All host-side: jax is
touched only for scalar coercion, one CPU profiler trace and one tiny
jit."""
import json
import math

import numpy as np
import pytest

from benchmarks.check_schemas import check_telemetry_jsonl
from repro.obs import (
    NULL,
    InMemorySink,
    JSONLSink,
    MetricsRegistry,
    PrometheusTextfileSink,
    Telemetry,
    Tracer,
    chrome_trace_doc,
    load_chrome_trace,
    make_telemetry,
    write_chrome_trace,
)
from repro.obs.report import render
from repro.obs.telemetry import _NULL_INSTRUMENT, _NULL_SPAN, _jsonable


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.add(4)
    g = reg.gauge("g")
    g.set(2.5)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5


def test_registry_get_or_create_returns_same_instrument():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.gauge("y") is reg.gauge("y")
    assert reg.histogram("h") is reg.histogram("h")


def test_never_set_gauge_omitted_from_snapshot():
    reg = MetricsRegistry()
    reg.gauge("unset")
    assert "unset" not in reg.snapshot()["gauges"]


def test_histogram_count_sum_min_max_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in (0.01, 0.02, 0.03, 0.5):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["min"] == 0.01 and s["max"] == 0.5
    assert abs(s["sum"] - 0.56) < 1e-12
    assert abs(s["mean"] - 0.14) < 1e-12


def test_histogram_percentiles_ordered_and_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    vals = [0.001 * (i + 1) for i in range(200)]
    for v in vals:
        h.observe(v)
    s = h.snapshot()
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # interpolated p50 lands near the true median (bucket resolution)
    assert 0.05 <= s["p50"] <= 0.2


def test_histogram_empty_snapshot():
    reg = MetricsRegistry()
    s = reg.histogram("empty").snapshot()
    assert s["count"] == 0


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("fl.rounds").add(3)
    reg.gauge("fl.loss").set(0.5)
    reg.histogram("fl.round_seconds").observe(0.1)
    text = reg.prometheus_text()
    assert "fl_rounds 3" in text
    assert "fl_loss 0.5" in text
    assert "fl_round_seconds_count 1" in text
    assert 'le="+Inf"' in text


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_chrome_doc(tmp_path):
    tr = Tracer()
    with tr.span("outer", round=1):
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    assert tr.spans[0].depth == 1 and tr.spans[1].depth == 0

    doc = chrome_trace_doc(tr.spans, process_name="test")
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert e["dur"] >= 0 and isinstance(e["ts"], (int, float))

    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), tr.spans, process_name="test")
    loaded = load_chrome_trace(str(path))
    assert {e["name"] for e in loaded["traceEvents"]
            if e["ph"] == "X"} == {"outer", "inner"}


def test_span_records_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("failing"):
            raise ValueError("boom")
    assert [s.name for s in tr.spans] == ["failing"]


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_one_object_per_line(tmp_path):
    path = tmp_path / "run.jsonl"
    sink = JSONLSink(str(path))
    sink.emit({"kind": "a", "n": 1})
    sink.emit({"kind": "b", "n": 2})
    sink.close()
    lines = path.read_text().strip().splitlines()
    assert [json.loads(ln)["kind"] for ln in lines] == ["a", "b"]


def test_in_memory_sink_by_kind():
    sink = InMemorySink()
    sink.emit({"kind": "round", "n": 0})
    sink.emit({"kind": "round", "n": 1})
    sink.emit({"kind": "eval"})
    assert len(sink.by_kind("round")) == 2
    assert len(sink.events) == 3


def test_prometheus_textfile_sink(tmp_path):
    path = tmp_path / "metrics.prom"
    tel = Telemetry(run_id="t", sinks=[PrometheusTextfileSink(str(path))])
    tel.counter("serve.requests").add(7)
    tel.close()
    assert "serve_requests 7" in path.read_text()


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------

def test_make_telemetry_without_sinks_is_null():
    assert make_telemetry() is NULL
    assert not NULL.enabled


def test_null_telemetry_is_allocation_free():
    # disabled instruments and spans are preallocated module singletons —
    # the hot loop holds the same object no matter how often it asks
    assert NULL.counter("a") is NULL.counter("b") is _NULL_INSTRUMENT
    assert NULL.gauge("a") is NULL.histogram("b") is _NULL_INSTRUMENT
    assert NULL.span("s", x=1) is NULL.span("t") is _NULL_SPAN
    with NULL.span("s"):
        pass
    NULL.event("anything", x=1)
    NULL.close()


def test_event_envelope_and_jsonable_coercion():
    sink = InMemorySink()
    tel = Telemetry(run_id="r1", sinks=[sink])
    tel.event("round", loss=np.float32(0.5), n=np.int64(3),
              arr=np.arange(2), nested={"x": np.float64(1.0)})
    ev = sink.by_kind("round")[0]
    assert ev["run_id"] == "r1" and "ts" in ev
    assert ev["loss"] == 0.5 and ev["n"] == 3
    assert ev["arr"] == [0, 1] and ev["nested"]["x"] == 1.0
    json.dumps(ev)   # strictly JSON-serializable


def test_jsonable_jax_scalar():
    import jax.numpy as jnp
    assert _jsonable(jnp.float32(2.0)) == 2.0
    assert _jsonable(jnp.int32(5)) == 5


def test_close_emits_metrics_snapshot_and_is_idempotent():
    sink = InMemorySink()
    tel = Telemetry(run_id="r", sinks=[sink])
    tel.counter("c").inc()
    tel.close()
    tel.close()
    metrics = sink.by_kind("metrics")
    assert len(metrics) == 1
    assert metrics[0]["metrics"]["counters"]["c"] == 1


def test_workload_stamps_run_meta():
    sink = InMemorySink()
    Telemetry(run_id="r", sinks=[sink], workload="serve")
    assert sink.by_kind("run_meta")[0]["workload"] == "serve"


# ---------------------------------------------------------------------------
# report CLI + JSONL validator
# ---------------------------------------------------------------------------

def _write_jsonl(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_report_renders_round_and_serving_sections(tmp_path):
    path = tmp_path / "run.jsonl"
    _write_jsonl(path, [
        {"ts": 1.0, "run_id": "r", "kind": "run_meta", "workload": "train"},
        {"ts": 1.1, "run_id": "r", "kind": "round", "round": 0, "loss": 0.9,
         "bytes_up": 100, "bytes_down": 50, "survivors": 3, "cohort": 4,
         "stragglers": 1},
        {"ts": 1.2, "run_id": "r", "kind": "eval", "round": 0, "acc": 0.75},
        {"ts": 1.3, "run_id": "r", "kind": "request", "request_id": "q0",
         "adapter_id": 1, "prompt_len": 8, "gen_tokens": 4, "ttft_s": 0.1,
         "latency_s": 0.2, "tok_per_sec": 20.0},
        {"ts": 1.4, "run_id": "r", "kind": "memory", "label": "post",
         "live_bytes": 1024},
        {"ts": 1.5, "run_id": "r", "kind": "metrics", "metrics": {
            "counters": {"adapter_cache.hits": 1,
                         "adapter_cache.misses": 1},
            "gauges": {"serve.decode_tok_per_sec": 33.3},
            "histograms": {}}},
    ])
    out = render(str(path))
    assert "== rounds ==" in out and "bytes_up_total=100" in out
    assert "== serving ==" in out and "q0" in out
    assert "33.3 tok/s" in out
    assert "hit rate 0.500" in out
    assert "== memory ==" in out
    assert "0.75" in out   # eval acc joined onto the round row


def test_report_rejects_bad_jsonl(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"ok": 1}\nnot json\n')
    with pytest.raises(ValueError):
        render(str(path))


def test_check_telemetry_jsonl_validator(tmp_path):
    good = tmp_path / "good.jsonl"
    _write_jsonl(good, [
        {"ts": 1.0, "run_id": "r", "kind": "round"},
        {"ts": 1.1, "run_id": "r", "kind": "metrics"},
    ])
    assert check_telemetry_jsonl(str(good),
                                 expect_kinds=("round", "metrics")) == []
    assert check_telemetry_jsonl(str(good), expect_kinds=("request",))

    bad = tmp_path / "bad.jsonl"
    _write_jsonl(bad, [{"kind": "round"}])   # missing ts/run_id envelope
    assert check_telemetry_jsonl(str(bad))

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert check_telemetry_jsonl(str(empty))


def test_memory_probe_emits_events(monkeypatch, tmp_path):
    from repro.obs import MemoryProbe, memory
    stats = {"tpu:0": {"bytes_in_use": 10, "peak_bytes_in_use": 100,
                       "peak_bytes_reserved": 23},
             "tpu:1": {"bytes_in_use": 5, "peak_bytes_in_use": 50}}
    monkeypatch.setattr(memory, "device_memory_stats", lambda: stats)
    sink = InMemorySink()
    tel = Telemetry(run_id="m", sinks=[sink])
    MemoryProbe(tel).sample("here")
    ev = sink.by_kind("memory")[0]
    assert ev["label"] == "here"
    # a device's peak is what it held in use plus what it reserved for
    # compiled programs' temporaries
    assert ev["device_peak_bytes"] == {"tpu:0": 123, "tpu:1": 50}
    assert ev["device_bytes_in_use"] == 15
    assert ev["live_bytes"] >= 0
    assert "modeled_peak_bytes" not in ev
    assert tel.metrics_snapshot()["gauges"]["mem.device_peak_bytes"] == 123
    path = tmp_path / "run.jsonl"
    _write_jsonl(path, sink.events)
    assert "device_peak" in render(str(path))


# ---------------------------------------------------------------------------
# profiler annotations + compile counter
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    opened: list = []

    def __init__(self, name):
        self.name = name
        _FakeAnnotation.opened.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_enabled_span_opens_a_trace_annotation(monkeypatch):
    from repro.obs import trace
    monkeypatch.setattr(trace, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.opened = []
    tel = Telemetry(run_id="a", sinks=[InMemorySink()])
    with tel.span("fl.round", round=3):
        with tel.span("fl.wire"):
            pass
    # the same names, no span args folded into them
    assert _FakeAnnotation.opened == ["fl.round", "fl.wire"]
    assert [s.name for s in tel.tracer.spans] == ["fl.wire", "fl.round"]
    # disabled telemetry opens none and allocates nothing
    _FakeAnnotation.opened = []
    with NULL.span("fl.round", round=3) as sp:
        assert sp is _NULL_SPAN
    assert _FakeAnnotation.opened == []


def test_span_annotation_lands_in_a_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tel = Telemetry(run_id="p", sinks=[InMemorySink()])
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("train.dispatch", round=0):
            pass
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "train.dispatch" in names


def test_compile_stats_counts_one_compile_of_a_fresh_function():
    import jax
    from repro.obs import compile_stats
    before = compile_stats()
    x = np.arange(7, dtype=np.float32)
    f = jax.jit(lambda v: v * 3.0 + before["compiles"])
    f(x)
    mid = compile_stats()
    f(x)                                    # cached: no second compile
    after = compile_stats()
    assert mid["compiles"] - before["compiles"] == 1
    assert after["compiles"] == mid["compiles"]
    assert mid["backend_s"] > before["backend_s"]
    assert mid["trace_s"] > before["trace_s"]
    assert mid["seconds"] > before["seconds"]
    assert mid["seconds"] <= (mid["trace_s"] + mid["lower_s"]
                              + mid["backend_s"]) + 1e-9
