"""Per-device memory accounting for the streaming build path (DESIGN.md
§13).

The sharded-from-birth corpus machinery exists to keep per-device memory
O(corpus / n_shards + chunk); this module is how that claim is *observed*
rather than asserted.  :func:`bytes_per_device` reads the allocator's
high-water mark where the platform exposes one (``device.memory_stats()``
on TPU/GPU), and falls back to summing the addressable shards of every
live ``jax.Array`` per device elsewhere (the CPU backend reports no
allocator stats) — the fallback is an instantaneous residency figure, not
a true peak, but it is exactly what the build keeps resident, which is the
quantity the streaming path bounds.

:func:`record_build_peak` publishes the worst device as the
``build.peak_bytes_per_device`` gauge; the session front doors call it
after every index / graph build so the figure lands in ``--metrics-json``
exports and the benchmark rows (benchmarks/run.py ``peak_bytes_per_device``
column).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from repro.obs.metrics import REGISTRY, Registry

__all__ = ["PEAK_GAUGE", "bytes_per_device", "record_build_peak",
           "resident_bytes_per_device"]

#: gauge name for the per-device build high-water mark
PEAK_GAUGE = "build.peak_bytes_per_device"


def _allocator_bytes(devices, keys) -> Optional[Dict[str, int]]:
    """device -> the first of ``keys`` its allocator reports, or None when
    any device reports none of them (CPU)."""
    per = {}
    for dev in devices:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # platform without allocator stats (CPU)
            return None
        val = next((stats[k] for k in keys if k in stats), None)
        if val is None:
            return None
        per[str(dev)] = int(val)
    return per


def _live_array_bytes(devices) -> Dict[str, int]:
    """device -> summed bytes of the addressable shards of live arrays."""
    per = {str(dev): 0 for dev in devices}
    for arr in jax.live_arrays():
        try:
            shards = arr.addressable_shards
        except Exception:
            continue
        for sh in shards:
            key = str(sh.device)
            if key in per and sh.data is not None:
                per[key] += int(sh.data.nbytes)
    return per


def bytes_per_device() -> Dict[str, int]:
    """device -> resident bytes: allocator peak where available, live-array
    shard accounting otherwise."""
    devices = jax.local_devices()
    return (_allocator_bytes(devices, ("peak_bytes_in_use", "bytes_in_use"))
            or _live_array_bytes(devices))


def resident_bytes_per_device() -> Dict[str, int]:
    """device -> bytes held right now: the allocator's ``bytes_in_use``
    where available, live-array shard accounting otherwise.  Unlike the
    peak it falls when arrays are freed, so two readings bracket what a
    stage left resident."""
    devices = jax.local_devices()
    return (_allocator_bytes(devices, ("bytes_in_use",))
            or _live_array_bytes(devices))


def record_build_peak(registry: Registry = REGISTRY) -> int:
    """Publish max-over-devices resident bytes as the build gauge."""
    per = bytes_per_device()
    peak = max(per.values(), default=0)
    registry.gauge(PEAK_GAUGE).set(peak)
    return int(peak)
