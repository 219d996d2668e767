"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``repro.roofline.analysis`` so that the yardstick does not move
with the program.  A device kind missing from the table is an error, never
a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    link_bw: float    # bytes/s per ICI link


#: ``device_kind`` of a TPU v5e chip, as JAX reports it
V5E = "TPU v5 lite"

#: TPU v5e -- Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per
#: chip over its 4 ICI links (50 GB/s each).
PEAKS = {
    V5E: Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for a kind the table
    does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
