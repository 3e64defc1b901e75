"""Static-analysis suite (ISSUE 7): every rule class must (a) catch a
SEEDED violation on a synthetic program — the analyzer has teeth — and
(b) come back clean (golden) on a real registry family traced through the
real entry-point harness. Plus the shared jaxpr walker and the HLO-text
mirrors in launch/hlo_analysis.py.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import (
    assert_no_tangent_stack,
    entrypoints as eps,
    kernel_name,
    kernel_src,
    kernel_vmem,
    pallas_calls,
    representative_kernel_rows,
    rules,
    tangent_stack_outputs,
    vmem_table,
)
from repro.analysis.vmem import VMEM_BYTES
from repro.kernels import dispatch
from repro.kernels.lora_dual.ops import lora_dual_mt, lora_dual_mt_jvps


def _lora_shapes(M=8, K=48, N=40, r=2, T=3):
    z = jnp.zeros
    x, w = z((M, K)), z((K, N))
    a, b = z((K, r)), z((r, N))
    ad, bd, xd = z((T, K, r)), z((T, r, N)), z((T, M, K))
    gy = z((M, N))
    return x, w, a, b, ad, bd, xd, gy


def _materializing_jaxpr(T=3):
    """The mt route: writes the (T,)+y tangent stack — the seeded
    violation the tangent rule must catch."""
    x, w, a, b, ad, bd, xd, gy = _lora_shapes(T=T)
    thunk = lambda: lora_dual_mt(x, xd, w, a, ad, b, bd, interpret=True)
    return jax.make_jaxpr(thunk)(), T, gy.shape


def _epilogue_jaxpr(T=3):
    """The jvps contraction route: per-block partials only — clean."""
    x, w, a, b, ad, bd, xd, gy = _lora_shapes(T=T)
    thunk = lambda: lora_dual_mt_jvps(x, w, a, ad, b, bd, gy, xdots=xd,
                                      impl="kernel", interpret=True)
    return jax.make_jaxpr(thunk)(), T, gy.shape


# ---------------------------------------------------------------------------
# shared walker
# ---------------------------------------------------------------------------

def test_walker_finds_pallas_calls_through_nesting():
    x, w, a, b, ad, bd, xd, gy = _lora_shapes()
    # wrap in jit so the pallas_call sits under a pjit sub-jaxpr
    thunk = jax.jit(lambda: lora_dual_mt(x, xd, w, a, ad, b, bd,
                                         interpret=True))
    jaxpr = jax.make_jaxpr(thunk)()
    calls = pallas_calls(jaxpr)
    assert calls, "walker lost the pallas_call nested under pjit"
    assert kernel_name(calls[0]) == "_mt_kernel"
    assert "lora_dual" in kernel_src(calls[0])


def _family_thunks():
    from repro.kernels.lora_dual.ops import lora_dual_multi
    from repro.kernels.mamba2_scan.ops import mamba2_scan
    from repro.kernels.swa_attention.ops import swa_attention
    from repro.kernels.wkv6_scan.ops import wkv6_scan
    z = jnp.zeros
    seq = z((1, 8, 2, 8))
    return {
        "lora_dual": ("_multi_kernel", lambda: lora_dual_multi(
            z((2, 16)), z((2,), jnp.int32), z((16, 16)), z((2, 16, 1)),
            z((2, 1, 16)), interpret=True)),
        "wkv6_scan": ("_kernel", lambda: wkv6_scan(
            seq, seq, seq, seq, z((2, 8)), interpret=True)),
        "mamba2_scan": ("_kernel", lambda: mamba2_scan(
            seq, z((1, 8, 4)), z((1, 8, 4)), z((1, 8, 2)), interpret=True)),
        "swa_attention": ("_kernel", lambda: swa_attention(
            z((1, 2, 16, 8)), z((1, 2, 16, 8)), z((1, 2, 16, 8)), window=8,
            interpret=True)),
    }


@pytest.mark.parametrize("family", ["lora_dual", "wkv6_scan", "mamba2_scan",
                                    "swa_attention"])
def test_kernel_name_resolves(family):
    """The walker names each kernel family's pallas_call by its kernel
    function and source file (the family filters and VMEM rows key on
    it)."""
    name, thunk = _family_thunks()[family]
    (call,) = pallas_calls(jax.make_jaxpr(thunk)())
    assert kernel_name(call) == name
    assert f"kernels/{family}/kernel.py" in kernel_src(call)


# ---------------------------------------------------------------------------
# rule 1: tangent-materialization
# ---------------------------------------------------------------------------

def test_tangent_rule_catches_materializing_route():
    jaxpr, T, y_shape = _materializing_jaxpr()
    hits = tangent_stack_outputs(jaxpr, T, y_shape)
    assert hits, "seeded tangent stack not detected"
    with pytest.raises(AssertionError, match="tangent-stack-sized"):
        assert_no_tangent_stack(jaxpr, T, y_shape)
    findings = rules.check_tangent_stack("toy.mt", jaxpr, T, y_shape,
                                         expect_epilogue=False)
    assert any(f.severity == "error" for f in findings)


def test_tangent_rule_passes_epilogue_route():
    jaxpr, T, y_shape = _epilogue_jaxpr()
    assert rules.check_tangent_stack("toy.jvps", jaxpr, T, y_shape) == []
    assert_no_tangent_stack(jaxpr, T, y_shape)


# ---------------------------------------------------------------------------
# rule 2: vmem-budget
# ---------------------------------------------------------------------------

def test_vmem_rows_within_budget_and_seeded_overflow(monkeypatch):
    jaxpr, _, _ = _epilogue_jaxpr()
    rows = vmem_table(jaxpr)
    assert rows and all(r["ok"] for r in rows)
    row = rows[0]
    assert row["residency_bytes"] == (2 * row["block_bytes"]
                                      + row["scratch_bytes"])
    assert rules.check_vmem("toy.jvps", jaxpr) == []
    # seed an overflow: a 1 KiB budget no kernel fits
    monkeypatch.setitem(VMEM_BYTES, "tiny", 1024)
    findings = rules.check_vmem("toy.jvps", jaxpr, generation="tiny")
    assert findings and all(f.severity == "error" for f in findings)
    assert not kernel_vmem(pallas_calls(jaxpr)[0], "tiny")["ok"]


def test_representative_kernel_table_covers_all_families():
    rows = representative_kernel_rows()
    fams = {r["family"] for r in rows}
    assert {"lora_dual", "wkv6_scan", "swa_attention", "mamba2_scan"} <= fams
    assert all(r["ok"] for r in rows), [r["kernel"] for r in rows
                                        if not r["ok"]]
    assert rules.check_vmem_rows("kernels.representative", rows) == []


# ---------------------------------------------------------------------------
# rule 3: transpose-reachability
# ---------------------------------------------------------------------------

def test_transpose_rule_catches_seeded_kernel_in_reverse_trace():
    # hand the checker a trace that DOES contain pallas_calls, standing in
    # for a reverse-mode trace that reached a kernel
    jaxpr, _, _ = _materializing_jaxpr()
    findings = rules.check_transpose_reachability("toy.reverse", jaxpr)
    assert findings and all(f.severity == "error" for f in findings)
    assert "transpose" in findings[0].message


def test_transpose_rule_clean_on_grad_outside_region():
    x = jnp.zeros((8, 48))
    w = jnp.zeros((48, 40))
    peft = {"A": jnp.zeros((48, 2)), "B": jnp.zeros((2, 40))}

    def loss(p):
        y = dispatch.lora_proj(x, w, p["A"], p["B"], 2.0)
        return jnp.mean(y * y)

    dispatch.set_backend("interpret")
    try:
        g_jaxpr = jax.make_jaxpr(jax.grad(loss))(peft)
    finally:
        dispatch.set_backend(None)
    assert rules.check_transpose_reachability("toy.grad", g_jaxpr) == []


# ---------------------------------------------------------------------------
# rule 4: donation
# ---------------------------------------------------------------------------

def _toy_step_lowered(donate):
    state = jnp.zeros((512, 512), jnp.float32)      # exactly 1 MiB
    x = jnp.float32(0.0)

    def step(s, x):
        return s + x, jnp.sum(s)

    kw = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(step, **kw).lower(state, x)


def test_donation_rule_catches_undonated_carried_state():
    findings = rules.check_donation("toy.step", _toy_step_lowered(False))
    assert any(f.severity == "error" and "donate_argnums" in f.message
               for f in findings)


def test_donation_rule_clean_when_donated_and_waivable():
    assert rules.check_donation("toy.step", _toy_step_lowered(True)) == []
    waived = rules.check_donation("toy.step", _toy_step_lowered(False),
                                  waivers={"toy.step": "toy reason"})
    assert waived and all(f.severity == "info" for f in waived)
    assert "toy reason" in waived[0].message


# ---------------------------------------------------------------------------
# rule 5: dtype-policy
# ---------------------------------------------------------------------------

def _bad_dtype_jaxpr():
    """A kernel that seeds BOTH violations: f16 scratch accumulator and an
    in-kernel dot_general accumulating in f16."""
    def kernel(x_ref, o_ref, acc_ref):
        acc_ref[...] = jax.lax.dot_general(
            x_ref[...], x_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float16)
        o_ref[...] = acc_ref[...].astype(jnp.float32)

    def thunk():
        x = jnp.zeros((8, 8), jnp.float16)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 8), jnp.float16)],
            interpret=True)(x)

    return jax.make_jaxpr(thunk)()


def test_dtype_rule_catches_seeded_f16_accumulators():
    findings = rules.check_dtype_policy("toy.bad", _bad_dtype_jaxpr())
    msgs = " | ".join(f.message for f in findings)
    assert any(f.severity == "error" for f in findings)
    assert "scratch" in msgs and "dot_general" in msgs


def test_dtype_rule_clean_on_real_kernel_and_wire_table():
    jaxpr, _, _ = _epilogue_jaxpr()
    assert rules.check_dtype_policy("toy.jvps", jaxpr) == []
    assert not [f for f in rules.check_wire_dtypes()
                if f.severity == "error"]


# ---------------------------------------------------------------------------
# golden: one real family (ssm — the cheapest full-model trace) per rule
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ssm_loss_traces():
    return eps.loss_traces("ssm", "cls", K=4)


def test_golden_ssm_fused_clean(ssm_loss_traces):
    fused, _ = ssm_loss_traces
    assert fused.kind == "fused_loss"
    assert rules.check_tangent_stack(fused.name, fused.jaxpr, fused.K,
                                     fused.y_shape,
                                     family=fused.site_family) == []
    assert rules.check_vmem(fused.name, fused.jaxpr) == []
    assert rules.check_dtype_policy(fused.name, fused.jaxpr) == []


def test_golden_ssm_standard_route_has_teeth(ssm_loss_traces):
    _, std = ssm_loss_traces
    (teeth,) = rules.record_expected_stack(std.name, std.jaxpr, std.K,
                                           std.y_shape,
                                           family=std.site_family)
    assert teeth.severity == "info" and "teeth" in teeth.message


def test_golden_ssm_grad_guard_clean():
    (tr,) = eps.grad_guard_traces("ssm")
    assert rules.check_transpose_reachability(tr.name, tr.jaxpr) == []


def test_golden_ssm_serve_donation_clean():
    for tr in eps.serve_lowered("ssm"):
        bad = [f for f in rules.check_donation(tr.name, tr.lowered)
               if f.severity == "error"]
        assert bad == [], f"{tr.name}: {bad}"


# ---------------------------------------------------------------------------
# report plumbing + the HLO-text mirrors
# ---------------------------------------------------------------------------

def test_report_doc_shape(tmp_path):
    from repro.analysis.report import summarize, to_doc, write_analysis

    jaxpr, _, _ = _epilogue_jaxpr()
    rows = vmem_table(jaxpr)
    findings = [rules.Finding("donation", "error", "ep", "x", "msg"),
                rules.Finding("vmem-budget", "info", "ep", "y", "msg2")]
    doc = to_doc(findings, rows, ["ep"], "v5e", VMEM_BYTES["v5e"])
    assert doc["schema"] == "repro.analysis/v1"
    assert doc["summary"]["errors"] == 1 and doc["summary"]["info"] == 1
    assert summarize(findings)["errors"] == 1
    path = tmp_path / "ANALYSIS.json"
    write_analysis(path, doc)
    import json
    assert json.load(open(path))["budget"]["generation"] == "v5e"


_HLO = """\
HloModule toy, input_output_alias={ {0}: (0, {}, may-alias) }, \
entry_computation_layout={(f32[1024,1024]{1,0}, f32[1024,1024]{1,0})->\
(f32[1024,1024]{1,0})}
"""


def test_hlo_text_alias_and_donation_helpers():
    from repro.launch.hlo_analysis import (
        entry_parameter_bytes,
        parse_input_output_aliases,
        undonated_param_bytes,
    )
    assert parse_input_output_aliases(_HLO) == {0: 0}
    assert entry_parameter_bytes(_HLO) == [4 << 20, 4 << 20]
    assert undonated_param_bytes(_HLO) == [(1, 4 << 20)]
    no_alias = _HLO.replace("input_output_alias={ {0}: (0, {}, may-alias) }, ",
                            "")
    assert parse_input_output_aliases(no_alias) == {}
    assert undonated_param_bytes(no_alias) == [(0, 4 << 20), (1, 4 << 20)]
