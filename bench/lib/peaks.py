"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.  A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes: float       # bytes
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s, all inter-chip links of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9, hbm_bw=819e9,
        ici_bw=1600e9 / 8,
        source="Google Cloud documentation, TPU v5e system architecture"),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind the table lacks raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
