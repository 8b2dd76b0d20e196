"""Published peaks of each device, keyed by ``device_kind`` as JAX
reports it. A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to benchmark/harness/"
                       f"peaks.py with their source")
    return PEAKS[device_kind][what]
