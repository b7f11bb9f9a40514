"""TPU-mode attribute derivation (DESIGN.md §2 hardware adaptation).

The paper's PAPI attributes (L1/L2 miss rate, disk I/O, network I/O,
instruction count) have no TPU equivalents; their *roles* map to cost-model
quantities available from the dry-run / compiled step:

    l1_miss_rate  -> vmem pressure proxy:  bytes / (flops / MXU_intensity)
    l2_miss_rate  -> HBM boundedness:      bytes/flop relative to ridge point
    disk_io       -> host I/O bytes (data pipeline + checkpoint writes)
    network_io    -> collective bytes
    instructions  -> HLO flops

These keep the rough-set layer unchanged: a region whose 'l2' flag is 1 is
HBM-bandwidth-bound (the moral equivalent of a cache-missing loop on 2010
Opterons), one whose 'network_io' flag is 1 is collective-bound, etc.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping

import numpy as np


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    link_bw: float        # bytes/s per inter-chip link


#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
#: 1,600 Gbit/s of inter-chip interconnect over four links.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                               link_bw=50e9),
}

#: The chip the ``tpu`` schema's ratios are written against off-chip (CPU
#: rehearsals, stored windows).
NOMINAL_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of ``kind``; a chip that is not in the table is an error, never
    silently another chip's numbers."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(DEVICE_PEAKS)}); add its entry to "
                       f"perfdbg.attributes.DEVICE_PEAKS") from None


_NOMINAL = DEVICE_PEAKS[NOMINAL_KIND]
PEAK_FLOPS = _NOMINAL.flops
HBM_BW = _NOMINAL.hbm_bw
LINK_BW = _NOMINAL.link_bw
RIDGE_INTENSITY = PEAK_FLOPS / HBM_BW   # ~240 flops/byte


def region_attributes(flops: np.ndarray, bytes_hbm: np.ndarray,
                      collective_bytes: np.ndarray,
                      host_io_bytes: np.ndarray) -> Dict[str, np.ndarray]:
    """Build the paper's five attribute matrices from per-region cost terms.
    All inputs are (m_shards, n_regions)."""
    flops = np.maximum(np.asarray(flops, dtype=np.float64), 1.0)
    bytes_hbm = np.asarray(bytes_hbm, dtype=np.float64)
    intensity = flops / np.maximum(bytes_hbm, 1.0)
    return {
        "l1_miss_rate": np.clip(1.0 - intensity / RIDGE_INTENSITY, 0.0, 1.0) * 0.5,
        "l2_miss_rate": np.clip(1.0 - intensity / RIDGE_INTENSITY, 0.0, 1.0),
        "disk_io": np.asarray(host_io_bytes, dtype=np.float64),
        "network_io": np.asarray(collective_bytes, dtype=np.float64),
        "instructions": flops,
    }


def roofline_terms(flops: float, bytes_hbm: float, collective_bytes: float
                   ) -> Dict[str, float]:
    """Per-device three-term roofline (seconds)."""
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_hbm / HBM_BW,
        "collective_s": collective_bytes / LINK_BW,
    }


def dominant_term(terms: Mapping[str, float]) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k]).replace("_s", "")
