"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a share of a peak is only worth its denominator."""
from __future__ import annotations

V5E_SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": V5E_SOURCE},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
