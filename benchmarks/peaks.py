"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``:
the only source of a peak in the benchmark. A kind that is not listed is
an error, never a default."""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device_kind {device_kind!r}; "
            f"add it to benchmarks/peaks.py with its source "
            f"(known: {sorted(DEVICE_PEAKS)})") from None
