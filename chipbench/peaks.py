"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from ``repro.launch.analysis.PEAKS`` so that the yardstick lives with
the benchmark.  Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s
in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s of one chip
    hbm_bw: float  # HBM bytes/s of one chip
    hbm_bytes: float  # HBM capacity of one chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None
