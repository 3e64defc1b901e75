"""Pallas TPU kernels for the compute hot-spots of SPRY finetuning.

lora_dual/     fused LoRA primal+tangent matmul — the forward-mode AD
               hot-spot (paper §5.3 jvp overhead, removed on TPU by fusing
               tangent propagation into the same VMEM-resident pass). The
               multi-tangent (mt) variants stack K tangents on a leading
               axis so ONE pass over x/W serves the primal and all K jvp
               columns (the batched K-perturbation estimator's hot loop).
dispatch.py    backend routing for the fused LoRA projection: models'
               ``proj`` differentiates through the Pallas kernel on TPU and
               the jnp reference mirror on CPU (REPRO_LORA_BACKEND override).
swa_attention/ sliding-window flash attention (gemma3 / h2o-danube / zamba2)
wkv6_scan/     RWKV6 data-dependent-decay recurrence, parallel over
               (batch, heads) and chunk-parallel over the sequence: pairwise
               decays inside sub-chunks, the state carried across them on
               the MXU, two heads of 64 side by side on the lanes; tangents
               by the product rule through the same primal pieces
mamba2_scan/   Mamba2 state recurrence (zamba2 hybrid blocks): a per-token
               state walk with a VMEM tangent-state scratch

Every family also ships a ``*_mt_jvps`` contraction epilogue (lora / wkv6 /
swa): when the estimator knows the site's output cotangent gy, the T
tangent outputs are contracted against it blockwise in VMEM and never
written to HBM — see dispatch.py "Cotangent-known route".

Each kernel ships ops.py (jit'd dispatch wrapper) and ref.py (pure-jnp
oracle). Tests sweep shapes/dtypes in interpret mode (CPU) and assert
allclose against the oracle. Every kernel entry point takes ``interpret``
as a required keyword: the dispatch layer passes ``interpret=False`` on the
'pallas' backend (compiled Mosaic kernels on a TPU) and ``True`` on
'interpret'; ``tests/test_tpu_compile.py`` compiles each entry point for a
described v5e chip.
"""
import jax
import jax.numpy as jnp


def mxu_dot(a, b):
    """``jnp.dot`` with an f32 result, for kernel bodies.

    Operands of mixed dtype are promoted first, as the jnp mirrors promote
    them (``x.astype(a.dtype) @ a``). A bf16 x bf16 product is exact in the
    f32 accumulator, so such a dot states DEFAULT precision: Mosaic takes
    the fp32 contract precision that ``jax_default_matmul_precision=
    "highest"`` asks for only on f32 operands, and refuses to compile it on
    bf16 ones. f32 dots follow the configured precision."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    precision = jax.lax.Precision.DEFAULT if dt == jnp.bfloat16 else None
    return jnp.dot(a.astype(dt), b.astype(dt), precision=precision,
                   preferred_element_type=jnp.float32)


def sum_partials(parts):
    """(..., T, 1) per-block jvp partials of a ``*_mt_jvps`` kernel -> (T,).

    One row reduction per tangent: each lane sums its partials in the same
    order whatever T is, so stacked tangents stay bitwise-equal to
    single-tangent passes (a reduction over the leading axes of the whole
    block lets XLA pick a T-dependent order)."""
    T = parts.shape[-2]
    return parts.reshape(-1, T).T.sum(axis=1)
