"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB of HBM2 at
819 GB/s.  A chip that is not in the table is an error, never a
default: a share of an unknown peak would be a guess.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16 * 2**30},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates known for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
