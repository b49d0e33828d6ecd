"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default: a
share of a peak read on an unknown device (the CPU among them) means
nothing.

TPU v5e ("TPU v5 lite" to JAX): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. The roofline
table of the program (``src/repro/core/roofline.py``, ``DEVICE_KIND_SPECS``)
holds the same numbers but maps the CPU to them; this table does not.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # bytes/s
    hbm_bytes: float         # bytes


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None
