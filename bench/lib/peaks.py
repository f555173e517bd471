"""Published peaks of one chip, keyed by `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
JAX reports a v5e's `device_kind` as "TPU v5 lite".  A kind that is not
here is an error, never a default.
"""
from __future__ import annotations

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
