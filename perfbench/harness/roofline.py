"""Published peaks by ``device_kind`` and the least time a piece of work
could take on the chip.

A roofline share is that least time over the time the device spent: the
larger of operations over the peak rate and bytes over the memory
bandwidth, divided by device time.  The operations and bytes come from
``perfbench/work/`` (the algorithm's work, worked out from shapes), never
from the compiled program, so a change to the implementation is read
against the same work.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")

#: which peak divides operations of each numeric type
OPS_PEAK = {"bf16": "bf16_flops_per_s", "int8": "int8_ops_per_s"}


class UnknownDevice(KeyError):
    """The chip's ``device_kind`` has no row in peaks.json."""


def peaks_for(device_kind: str, path: str = PEAKS_JSON) -> Dict[str, float]:
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device_kind "
                            f"{device_kind!r} in {path}; known: "
                            + ", ".join(sorted(table)))
    return table[device_kind]


def ops_peak(peaks: Dict[str, float], dtype: str) -> float:
    """Operations per second for work of ``dtype`` (bf16, int8 or f32;
    f32 is divided by the peak that peaks.json names for it)."""
    key = peaks["f32_divided_by"] if dtype == "f32" else OPS_PEAK[dtype]
    return float(peaks[key])


def least_time_s(ops: float, nbytes: float, peaks: Dict[str, float],
                 dtype: str) -> float:
    """max(ops / peak ops, bytes / peak HBM bandwidth)."""
    return max(ops / ops_peak(peaks, dtype),
               nbytes / float(peaks["hbm_bytes_per_s"]))
