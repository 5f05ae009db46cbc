"""Reduce a profiler trace to device busy time, on the host's clock.

``jax.profiler`` writes an ``.xplane.pb``; :func:`read_trace` reads it with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per operation that ran.  Busy time is the
union of those events; idle is the rest of the window.

Trace timestamps have their own origin.  The harness enters a
``perfbench.anchor`` annotation at a host time it reads with
``time.perf_counter_ns``; the annotation's place in the trace gives the
offset that puts every device event on the host's ``perf_counter`` clock,
the clock that the load generator, the window and (converted from wall
time) the program's spans use.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

ANCHOR = "perfbench.anchor"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of (n, 2) [start, end) intervals as sorted disjoint ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after every earlier end
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.nonzero(new)[0][1:] - 1, [len(iv) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of the part of [lo, hi) that ``merged`` intervals cover."""
    if hi <= lo or len(merged) == 0:
        return 0.0
    a = np.clip(merged[:, 0], lo, hi)
    b = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(b - a))


@dataclasses.dataclass
class DeviceTrace:
    """Device activity of one traced window, in host ``perf_counter``
    seconds.  ``busy[i]`` are the merged operation intervals of the i-th
    device that ran anything; ``events`` the (device, name, start, end)
    rows they came from."""

    busy: List[np.ndarray]
    events: List[Tuple[int, str, float, float]]

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi) in which an operation ran, averaged over
        the devices."""
        if not self.busy:
            return 0.0
        return float(np.mean([covered(b, lo, hi) for b in self.busy]))

    def busy_in(self, spans: Iterable[Tuple[float, float]]) -> float:
        """Device busy seconds inside the given host intervals."""
        return sum(self.busy_s(lo, hi) for lo, hi in spans)

    def op_seconds(self, lo: float, hi: float,
                   width: int = 100) -> Dict[str, float]:
        """Device seconds per operation inside [lo, hi), summed over
        devices and divided by their number.  An operation is named by the
        first ``width`` characters of its HLO text (name, shape, opcode)."""
        out: Dict[str, float] = {}
        for _, name, s, e in self.events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[name[:width]] = out.get(name[:width], 0.0) + d
        n = max(len(self.busy), 1)
        return {k: v / n for k, v in out.items()}

    def gaps(self, lo: float, hi: float) -> np.ndarray:
        """Idle intervals of device 0 inside [lo, hi)."""
        b = self.busy[0] if self.busy else np.zeros((0, 2))
        b = b[(b[:, 1] > lo) & (b[:, 0] < hi)]
        edges = np.concatenate([[lo], np.clip(b.ravel(), lo, hi), [hi]])
        g = edges.reshape(-1, 2)
        return g[g[:, 1] > g[:, 0]]


def read_trace(path: str, anchor_perf_ns: int) -> DeviceTrace:
    """Device operations of the ``.xplane.pb`` at ``path``, moved onto the
    host clock by the anchor annotation entered at ``anchor_perf_ns``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    anchor: Optional[float] = None
    raw: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                rows = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
                if rows:                  # a chip the run did not use
                    raw.setdefault(plane.name, []).extend(rows)
            elif not is_device and anchor is None:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns
                        break
    if anchor is None:
        raise ValueError(f"{path}: no {ANCHOR!r} annotation in the trace")
    offset = anchor_perf_ns - anchor
    busy, events = [], []
    for dev, plane in enumerate(sorted(raw, key=_plane_order)):
        rows = raw[plane]
        iv = np.array([(s, e) for _, s, e in rows], np.float64)
        iv = (iv.reshape(-1, 2) + offset) * 1e-9
        busy.append(merge(iv))
        events.extend((dev, name, float(a), float(b))
                      for (name, _, _), (a, b) in zip(rows, iv))
    return DeviceTrace(busy, events)


def _plane_order(name: str) -> int:
    return int(name.rsplit(":", 1)[1])


def attribute(gaps: np.ndarray,
              spans: Sequence[Tuple[str, float, float]],
              reach: int = 64) -> Dict[str, float]:
    """Idle seconds per name of the innermost host span that covers each
    gap's midpoint (``spans`` are (name, start, end) on the same clock);
    ``"outside spans"`` where none does.  The innermost is the latest
    started of those that cover it, looked for among the ``reach`` spans
    that started last before the midpoint."""
    out: Dict[str, float] = {}
    ordered = sorted(spans, key=lambda s: s[1])
    starts = [s for _, s, _ in ordered]
    for lo, hi in gaps:
        mid = 0.5 * (lo + hi)
        name = "outside spans"
        i = bisect.bisect_right(starts, mid)
        for sname, _, end in reversed(ordered[max(0, i - reach):i]):
            if end >= mid:
                name = sname
                break
        out[name] = out.get(name, 0.0) + float(hi - lo)
    return out


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as [name, seconds] pairs."""
    return [[k, v] for k, v in sorted(items.items(),
                                      key=lambda kv: -kv[1])[:n]]
