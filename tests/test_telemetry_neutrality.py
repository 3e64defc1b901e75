"""Telemetry must be a pure observer: enabling it changes NOTHING the
workloads compute. Engine round state/metrics and ServingEngine token ids
are asserted bitwise identical with telemetry on vs off, and the
instrumented entry points must lower to identical HLO either way (the same
invariant the repro.analysis telemetry-neutrality rule enforces in CI)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SpryConfig, get_config, reduce_config
from repro.core import init_state
from repro.fl.runtime import FederationEngine, SerialExecutor, WireConfig
from repro.launch.adapter_cache import AdapterCache, SyntheticAdapterStore
from repro.launch.serving import Request, ServingEngine
from repro.models import get_model
from repro.obs import InMemorySink, Telemetry
from repro.peft import init_peft

ARCH = "rwkv6-1.6b"


def _fed_setup(M=3, B=2, S=16):
    cfg = reduce_config(get_config(ARCH))
    sc = SpryConfig(n_clients_per_round=M, local_iters=1, local_lr=1e-2,
                    server_lr=1e-2, k_perturbations=2)
    key = jax.random.PRNGKey(0)
    model = get_model(cfg)
    base = model.init_base(cfg, key)
    peft = init_peft(cfg, key, sc)
    state = init_state(base, peft)
    batch = {"tokens": jax.random.randint(key, (M, B, S), 0, cfg.vocab),
             "labels": jax.random.randint(key, (M, B), 0, cfg.n_classes)}
    return cfg, sc, state, batch


def _assert_trees_equal(a, b, what=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def test_engine_round_bitwise_identical_with_telemetry():
    cfg, sc, state, batch = _fed_setup()

    eng_off = FederationEngine(cfg, sc, task="cls")
    s_off, m_off = eng_off.run_ideal(state, batch)

    sink = InMemorySink()
    tel = Telemetry(run_id="t", sinks=[sink])
    eng_on = FederationEngine(cfg, sc, task="cls", telemetry=tel)
    s_on, m_on = eng_on.run_ideal(state, batch)

    _assert_trees_equal(s_off.peft, s_on.peft, "peft")
    _assert_trees_equal(s_off.server, s_on.server, "server state")
    _assert_trees_equal(m_off, m_on, "metrics")
    # ...and the instrumented run actually recorded the round
    rounds = sink.by_kind("round")
    assert len(rounds) == 1
    assert rounds[0]["loss"] == float(m_on["loss"])
    assert rounds[0]["survivors"] == 3 and rounds[0]["cohort"] == 3
    assert tel.registry.counter("fl.rounds").value == 1


def test_engine_wire_sim_bitwise_identical_with_telemetry():
    cfg, sc, state, batch = _fed_setup()
    wire = WireConfig(dtype="fp32", simulate=True)

    s_off, m_off = FederationEngine(
        cfg, sc, task="cls", wire=wire).run_ideal(state, batch)
    sink = InMemorySink()
    s_on, m_on = FederationEngine(
        cfg, sc, task="cls", wire=wire,
        telemetry=Telemetry(run_id="t", sinks=[sink])).run_ideal(state, batch)

    _assert_trees_equal(s_off.peft, s_on.peft, "peft (wire-sim)")
    _assert_trees_equal(m_off, m_on, "metrics (wire-sim)")
    assert sink.by_kind("round")[0]["bytes_up"] > 0


def _serving_outputs(telemetry):
    cfg = reduce_config(get_config(ARCH))
    model = get_model(cfg)
    base = model.init_base(cfg, jax.random.PRNGKey(0))
    store = SyntheticAdapterStore(cfg)
    cache = AdapterCache(store, capacity=2, telemetry=telemetry)
    eng = ServingEngine(cfg, base, cache, max_batch=2, cache_len=16,
                        telemetry=telemetry)
    rng = np.random.default_rng(3)
    reqs = [Request(request_id=f"q{i}", adapter_id=i,
                    prompt=rng.integers(0, cfg.vocab, size=6).astype(
                        np.int32),
                    max_new_tokens=5)
            for i in range(3)]
    return eng.run(reqs), eng


def test_serving_token_ids_bitwise_identical_with_telemetry():
    out_off, _ = _serving_outputs(None)

    sink = InMemorySink()
    tel = Telemetry(run_id="s", sinks=[sink])
    out_on, eng_on = _serving_outputs(tel)

    assert out_off == out_on   # exact integer token ids, every request
    reqs = sink.by_kind("request")
    assert {e["request_id"] for e in reqs} == {"q0", "q1", "q2"}
    for e in reqs:
        assert e["gen_tokens"] == 5
        assert e["ttft_s"] >= 0 and e["latency_s"] >= e["ttft_s"]
    snap = tel.metrics_snapshot()
    assert snap["counters"]["serve.requests"] == 3
    assert snap["counters"]["serve.gen_tokens"] == 15
    assert snap["counters"]["adapter_cache.misses"] >= 3
    assert snap["histograms"]["serve.ttft_s"]["count"] == 3


def test_instrumented_entrypoints_lower_identically():
    """The jaxpr/HLO sweep: every telemetry-pair entry point must lower to
    byte-identical text with telemetry on vs off."""
    from repro.analysis.entrypoints import telemetry_pair_lowered
    from repro.analysis.rules import check_telemetry_neutrality

    traces = telemetry_pair_lowered("ssm")
    assert len(traces) >= 3   # engine round + serving decode1 + scatter
    for t in traces:
        findings = check_telemetry_neutrality(
            t.name, t.meta["text_off"], t.meta["text_on"])
        assert all(f.severity == "info" for f in findings), (
            t.name, [str(f) for f in findings])


def test_neutrality_rule_has_teeth():
    from repro.analysis.rules import check_telemetry_neutrality
    same = check_telemetry_neutrality("e", "aaa\nbbb", "aaa\nbbb")
    assert [f.severity for f in same] == ["info"]
    diff = check_telemetry_neutrality("e", "aaa\nbbb", "aaa\nccc")
    assert [f.severity for f in diff] == ["error"]
    assert diff[0].data["first_diff_line"] == 2


def test_chrome_trace_exports_spans_from_real_run(tmp_path):
    cfg, sc, state, batch = _fed_setup()
    tel = Telemetry(run_id="t", sinks=[InMemorySink()])
    FederationEngine(cfg, sc, task="cls", telemetry=tel).run_ideal(state,
                                                                   batch)
    path = tmp_path / "trace.json"
    tel.export_chrome_trace(str(path))
    from repro.obs import load_chrome_trace
    doc = load_chrome_trace(str(path))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "fl.round" in names


def test_round_loop_spans_and_profile_trace(tmp_path):
    """The in-process round loop spans each round's prep, dispatch and loss
    read (no span claims to be the round: the dispatch returns before the
    device finishes), counts the first round's compiles, and with a profile
    directory puts the spans in an xplane trace beside the device ops."""
    import glob

    from jax.profiler import ProfileData

    from repro.launch.train import run_training
    sink = InMemorySink()
    tel = Telemetry(run_id="t", sinks=[sink])
    losses = [h["loss"] for h in run_training(
        arch=ARCH, task="toy", method="spry", rounds=2, clients_per_round=2,
        total_clients=4, batch_size=2, eval_every=2, k_perturbations=2,
        telemetry=tel, profile_dir=str(tmp_path), log=lambda *a: None)]
    names = [s.name for s in tel.tracer.spans
             if s.name.startswith("train.")]
    assert names == ["train.prep", "train.dispatch", "train.loss_read"] * 2
    rounds = sink.by_kind("round")
    assert [ev["round"] for ev in rounds] == [0, 1]
    assert rounds[0]["compiles"] >= 1 and "compiles" not in rounds[1]
    assert rounds[1]["loss"] == losses[-1]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    traced = {e.name for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events}
    assert {"train.prep", "train.dispatch", "train.loss_read"} <= traced
