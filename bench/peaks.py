"""Published peak rates of the chips the benchmark runs on.

The benchmark's own table, kept apart from the program's so that no change
to the program can move a roofline share.  Keyed by
``jax.Device.device_kind``; a kind that is not here is an error.

``"TPU v5 lite"`` (TPU v5e): Google Cloud documentation, "TPU v5e" system
architecture page -- 197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add a row with its source to "
                         f"bench/peaks.py") from None
