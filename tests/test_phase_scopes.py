"""Every operation of the compiled Spry round step belongs to a phase.

The round step names its phases with ``jax.named_scope``: ``spry.primal``,
``spry.tangents``, ``spry.client_update`` and ``spry.server``
(core/spry.py, core/forward_grad.py, fl/server.py). The benchmark splits
the device time of a traced window by them (``bench/phases.py``, whose
rule these tests apply to the step's optimized HLO). The step is compiled
here, without a chip, for a described TPU v5e on the kernel backend that
runs there, at the tiny rwkv6 width (2 layers, d_model 256) that
``bench/tests`` use.
"""
from __future__ import annotations

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_config
from repro.core import init_state
from repro.kernels import dispatch
from repro.launch.train import build_round_step, spry_config
from repro.models import get_model
from repro.peft import init_peft

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)
import hlo  # noqa: E402
import phases  # noqa: E402

M, B, S, K = 4, 2, 16, 4
# case -> (method, fused contraction)
ROUTES = {"fused-k4": ("spry", True),
          "standard-k4": ("spry", False),
          "per-iteration-k4": ("spry_periter", False)}

# what may carry no phase, each with its reason: XLA and the TPU compiler
# make these after lowering, from no operation of the program
UNSCOPED_CUSTOM_CALLS = {
    "AllocateBuffer": "the TPU compiler's scratch buffer; it computes nothing",
    "ConcatBitcast": "the TPU compiler rejoins the halves of a weight it "
                     "copied in two slices; no operation of the program",
}
UNSCOPED_FUSION_OPS = {
    "parameter", "copy", "bitcast",  # a layout change XLA adds after lowering
}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)"
                    r"\s+([\w\-]+)\(")
_HEAD = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def step_hlo(one_chip):
    """route -> optimized HLO text of the jitted round step, compiled once."""
    cache = {}

    def get(route):
        if route not in cache:
            cache[route] = _compile(one_chip, *ROUTES[route])
        return cache[route]
    return get


def _compile(sharding, method, fused):
    cfg = reduce_config(get_config("rwkv6-1.6b"))
    sc = spry_config(method, n_clients_per_round=M, n_total_clients=8,
                     k_perturbations=K, fused_contraction=fused,
                     lora_targets=("wr", "wv"))
    model = get_model(cfg)
    state = jax.eval_shape(lambda k: init_state(
        model.init_base(cfg, k), init_peft(cfg, k, sc)),
        jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((M, B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((M, B), jnp.int32)}
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (state, batch))
    dispatch.set_backend("pallas")
    try:
        step = build_round_step(cfg, sc, method)[0]
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            *on_chip).compile()
    finally:
        dispatch.set_backend(None)
    return compiled.as_text()


def _work(hlo_text):
    """[(instruction, opcode, custom-call target or fused opcodes)] of every
    custom-call, dot, convolution and fusion instruction."""
    bodies, computation = {}, None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head and "=" not in line.split("{", 1)[0]:
            computation = head.group(1)
            bodies[computation] = set()
            continue
        instr = _INSTR.match(line)
        if instr and computation is not None:
            bodies[computation].add(instr.group(2))
    out = []
    for line in hlo_text.splitlines():
        instr = _INSTR.match(line)
        if not instr or instr.group(2) not in (
                "custom-call", "dot", "convolution", "fusion"):
            continue
        target = _TARGET.search(line)
        called = _CALLS.search(line)
        detail = (target.group(1) if target else
                  bodies.get(called.group(1), set()) if called else None)
        out.append((instr.group(1), instr.group(2), detail))
    return out


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_every_operation_has_a_phase(step_hlo, route):
    text = step_hlo(route)
    of = phases.instruction_phases(text)
    work = _work(text)
    assert sum(op in ("custom-call", "dot", "convolution")
               for _, op, _ in work) > 10
    escaped = []
    for name, op, detail in work:
        if of[name] is not None:
            continue
        if op == "custom-call" and detail in UNSCOPED_CUSTOM_CALLS:
            continue
        if op == "fusion" and detail and detail <= UNSCOPED_FUSION_OPS:
            continue
        escaped.append((name, op, detail))
    assert not escaped, f"operations outside every phase: {escaped}"
    seen = {of[name] for name, _, _ in work}
    assert set(phases.PHASES) <= seen


# (entry point, phase) on the fused route: the primal state walk runs once
# per estimate, the multi-tangent kernels evaluate the K tangents
FUSED_KERNELS = [("wkv6_scan", "primal"),
                 ("wkv6_scan_mt_tangents", "tangents"),
                 ("wkv6_scan_mt_jvps", "tangents"),
                 ("lora_dual_mt_tangents", "tangents")]


@pytest.mark.parametrize("entry,phase", FUSED_KERNELS)
def test_fused_route_kernel_phase(step_hlo, entry, phase):
    text = step_hlo("fused-k4")
    of = phases.instruction_phases(text)
    calls = [instr for instr, e in hlo.kernel_sites(text).items()
             if e == entry]
    assert calls, f"no {entry} call in the fused step"
    assert {of[instr] for instr in calls} == {phase}


def test_linear_map_ops_are_tangent_work():
    """The linear map replays the name stack it was built under inside the
    scope where it is applied: tangents wins over a later primal."""
    base = "jit(round_step)/spry.server/vmap(spry.client_update)/while/body"
    assert phases.phase_of(
        f"{base}/spry.tangents/vmap(spry.primal)/jvp(jit(k))/pallas_call"
    ) == "tangents"
    assert phases.phase_of(f"{base}/spry.primal/jvp()/dot_general") \
        == "primal"
    assert phases.phase_of(f"{base}/jit(_normal)/mul") == "client_update"
    assert phases.phase_of("jit(round_step)/spry.server/add") == "server"
    assert phases.phase_of("state.base['embed']") is None
