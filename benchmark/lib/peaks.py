"""Published peaks of the chips this benchmark has met, keyed by the exact
``device_kind`` string jax reports. An unknown kind raises: a utilization
against a guessed peak is worse than none.

Copied from ``bench._PEAK_FLOPS`` (PERF.md lists the original for deletion).
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect, per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no {what!r} peak for device_kind {device_kind!r}; add it to "
            "benchmark/lib/peaks.py with its source"
        ) from None
