"""Peaks of the chips the benchmark knows, keyed by jax's `device_kind`.
A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
bf16 and 394 TOP/s int8 per chip, 16 GB of HBM2e at 819 GB/s.
"""
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def of(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(f"perfbench: no peaks known for device kind "
                         f"{device_kind!r}; add it to harness/peaks.py with "
                         "its source")
    return PEAKS[device_kind]
