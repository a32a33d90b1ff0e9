"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A kind that is not in the table is an error, never a default: a share of
a peak measured against the wrong chip's peak means nothing.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 2**30,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GiB HBM2 at 819 GB/s per chip"),
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
