"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.  JAX
reports a v5e chip's ``device_kind`` as ``"TPU v5 lite"``.

A device that is not in the table is an error, never a default: a
utilization or roofline share against a guessed peak is not a number.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    source: str


_V5E = Peaks(
    bf16_flops=197e12,
    hbm_bytes_per_s=819e9,
    source="Google Cloud documentation, TPU v5e",
)

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; ``KeyError`` for a device not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
