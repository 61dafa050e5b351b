"""TPU v5e peak rates and on-chip memory, per chip."""

from __future__ import annotations

from typing import Dict

HW: Dict[str, float] = dict(
    peak_flops=197e12,      # bf16 FLOP/s
    hbm_bw=819e9,           # bytes/s
    vmem_bytes=16 * 2**20,  # ~16 MB/core on-chip vector memory
)
