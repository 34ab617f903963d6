"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share against a guessed peak means nothing.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # dense bf16 matrix FLOP/s of one chip
    hbm_bytes_s: float    # HBM bandwidth of one chip, bytes/s
    source: str


PEAKS = {
    # TPU v5e; JAX names it "TPU v5 lite"
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_s=819e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture "
               "page: 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)})") from None
