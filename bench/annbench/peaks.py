"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A kind
that is not here is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": dict(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9, source="Google Cloud, TPU v5e"),
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/annbench/peaks.py")
    return PEAKS[device_kind]
