"""Published peak rates of the accelerators this repository runs on.

One table, keyed by ``jax.Device.device_kind``.  The tile autotuner's cost
model and ``benchmarks/roofline.py`` read their peaks here and nowhere
else.  A device kind missing from the table is an error, not a default:
add its row, with its source, before tuning or modelling for it.

Source of the ``"TPU v5 lite"`` (TPU v5e) row: Google Cloud documentation,
"TPU v5e" system architecture page -- 197 TFLOP/s bf16 per chip, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip over
four ICI ports (so 50 GB/s per port).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float            # dense bf16 FLOP/s
    hbm_bytes_s: float      # HBM bandwidth, bytes/s
    hbm_bytes: float        # HBM capacity, bytes
    ici_link_bytes_s: float  # one inter-chip link, bytes/s per direction


PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bytes_s=819e9,
                               hbm_bytes=16e9, ici_link_bytes_s=50e9),
}

# What a CPU process (tests, Pallas interpret mode, offline modelling)
# tunes and models for: the chip the kernels are written for.
TUNING_TARGET = "TPU v5 lite"


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of ``kind``; by default of JAX's first device, where a CPU
    stands for ``TUNING_TARGET``.  Raises ``ValueError`` for a kind the
    table does not hold."""
    if kind is None:
        dev = jax.devices()[0]
        kind = TUNING_TARGET if dev.platform == "cpu" else dev.device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {kind!r}; add a row "
            f"with its source to repro.kernels.peaks.PEAKS") from None
