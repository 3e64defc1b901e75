"""Every Pallas kernel entry point that ``kernels/dispatch.py`` can reach,
compiled ahead of time for a described (not attached) TPU v5e chip at the
widths of the configs that run it.

The TPU compiler refuses what interpret mode accepts (block shapes not
tiled to (8, 128), unsupported vector layouts, too much VMEM), so these
compiles guard the chip path at no chip time. Each asserts that the kernel
is in the compiled HLO as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.lora_dual.ops import (
    lora_dual_mt_jvps,
    lora_dual_mt_tangents,
    lora_dual_multi,
)
from repro.kernels.mamba2_scan.ops import (
    mamba2_scan,
    mamba2_scan_mt_jvps,
    mamba2_scan_mt_tangents,
)
from repro.kernels.swa_attention.ops import (
    swa_attention,
    swa_attention_mt_jvps,
    swa_attention_mt_tangents,
)
from repro.kernels.wkv6_scan.ops import (
    wkv6_scan,
    wkv6_scan_mt_jvps,
    wkv6_scan_mt_tangents,
)

F32, BF16 = jnp.float32, jnp.bfloat16
T = 4          # tangents (K perturbations)
B, S = 2, 256  # batch rows, sequence


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
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _lora(d=2048, n=2048, r=8):
    """A rwkv6-1.6b / zamba2-1.2b LoRA site: d_model 2048, rank 8."""
    x = ((B, S, d), BF16)
    return dict(x=x, xd=((T, B, S, d), BF16), w=((d, n), BF16),
                a=((d, r), F32), ad=((T, d, r), F32), b=((r, n), F32),
                bd=((T, r, n), F32), gy=((B, S, n), BF16))


def _wkv6(h=32, hd=64):
    """rwkv6-1.6b: 32 heads of 64; ``seqt1``: one tangent, the K=1 route."""
    seq = ((B, S, h, hd), F32)
    return dict(seq=seq, u=((h, hd), F32), seqt=((T, B, S, h, hd), F32),
                seqt1=((1, B, S, h, hd), F32))


def _mamba2(h=64, hd=64, n=64):
    """zamba2-1.2b: expand 2 x d_model 2048 = 64 heads of 64, state 64."""
    return dict(x=((B, S, h, hd), F32), bc=((B, S, n), F32),
                dec=((B, S, h), F32), xt=((T, B, S, h, hd), F32),
                bct=((T, B, S, n), F32), dect=((T, B, S, h), F32))


def _swa(h=32, kv=8, hd=120):
    """h2o-danube-3-4b: 32 query heads over 8 kv heads of 120 (GQA, hd
    padded to the lane width in-op)."""
    return dict(q=((B, h, S, hd), BF16), kv=((B, kv, S, hd), BF16),
                qt=((T, B, h, S, hd), BF16), kvt=((T, B, kv, S, hd), BF16))


# entry point -> (op, operand specs, static kwargs)
def _cases():
    lo, wk, mb, sw = _lora(), _wkv6(), _mamba2(), _swa()
    return {
        "lora_dual_mt_tangents": (
            lora_dual_mt_tangents,
            [lo["x"], lo["xd"], lo["w"], lo["a"], lo["ad"], lo["b"],
             lo["bd"]], {}),
        "lora_dual_mt_tangents_no_xdot": (
            lambda x, w, a, ad, b, bd, **kw: lora_dual_mt_tangents(
                x, None, w, a, ad, b, bd, **kw),
            [lo["x"], lo["w"], lo["a"], lo["ad"], lo["b"], lo["bd"]], {}),
        "lora_dual_mt_jvps": (
            lambda x, w, a, ad, b, bd, gy, xd, **kw: lora_dual_mt_jvps(
                x, w, a, ad, b, bd, gy, xdots=xd, impl="kernel", **kw),
            [lo["x"], lo["w"], lo["a"], lo["ad"], lo["b"], lo["bd"],
             lo["gy"], lo["xd"]], {}),
        "lora_dual_mt_jvps_no_xdot": (
            lambda x, w, a, ad, b, bd, gy, **kw: lora_dual_mt_jvps(
                x, w, a, ad, b, bd, gy, impl="kernel", **kw),
            [lo["x"], lo["w"], lo["a"], lo["ad"], lo["b"], lo["bd"],
             lo["gy"]], {}),
        "lora_dual_multi": (
            lora_dual_multi,
            [((8, 1, 2048), BF16), ((8,), jnp.int32), ((2048, 2048), BF16),
             ((4, 2048, 8), F32), ((4, 8, 2048), F32)], {}),
        "wkv6_scan": (wkv6_scan, [wk["seq"]] * 4 + [wk["u"]], {}),
        "wkv6_scan_mt_tangents": (
            wkv6_scan_mt_tangents,
            [wk["seq"]] * 4 + [wk["u"]] + [wk["seqt"]] * 4, {}),
        "wkv6_scan_mt_tangents_t1": (
            wkv6_scan_mt_tangents,
            [wk["seq"]] * 4 + [wk["u"]] + [wk["seqt1"]] * 4, {}),
        "wkv6_scan_mt_jvps": (
            wkv6_scan_mt_jvps,
            [wk["seq"]] * 4 + [wk["u"]] + [wk["seqt"]] * 4 + [wk["seq"]],
            {}),
        "mamba2_scan": (mamba2_scan, [mb["x"], mb["bc"], mb["bc"],
                                      mb["dec"]], {}),
        "mamba2_scan_mt_tangents": (
            mamba2_scan_mt_tangents,
            [mb["x"], mb["bc"], mb["bc"], mb["dec"], mb["xt"], mb["bct"],
             mb["bct"], mb["dect"]], {}),
        "mamba2_scan_mt_jvps": (
            mamba2_scan_mt_jvps,
            [mb["x"], mb["bc"], mb["bc"], mb["dec"], mb["xt"], mb["bct"],
             mb["bct"], mb["dect"], mb["x"]], {}),
        "swa_attention": (swa_attention, [sw["q"], sw["kv"], sw["kv"]],
                          {"window": 128}),
        "swa_attention_mt_tangents": (
            swa_attention_mt_tangents,
            [sw["q"], sw["kv"], sw["kv"], sw["qt"], sw["kvt"], sw["kvt"]],
            {"window": 128}),
        "swa_attention_mt_jvps": (
            swa_attention_mt_jvps,
            [sw["q"], sw["kv"], sw["kv"], sw["qt"], sw["kvt"], sw["kvt"],
             sw["q"]], {"window": 128}),
    }


@pytest.mark.parametrize("entry", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, entry):
    op, specs, kw = _cases()[entry]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(lambda *a: op(*a, interpret=False, **kw)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("entry", sorted(_cases()))
def test_kernel_compiles_for_v5e_at_highest_precision(one_chip, entry):
    """Under ``jax_default_matmul_precision="highest"`` every f32 dot in a
    kernel body asks Mosaic for fp32 contract precision, which it refuses
    on bf16 operands; the kernels must still compile."""
    op, specs, kw = _cases()[entry]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(lambda *a: op(*a, interpret=False, **kw)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
