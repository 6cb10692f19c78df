"""Published peaks of the chips the benchmark may run on, keyed by what
``jax.devices()[0].device_kind`` reports.  A kind that is not here is an
error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 819 GB/s HBM2e, 16 GB per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmark/peaks.py "
                       f"with its source") from None
