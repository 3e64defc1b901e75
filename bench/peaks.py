"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device kind that is not here is an error: no number is made up for it.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py "
                         f"with their source") from None
