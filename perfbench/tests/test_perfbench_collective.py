"""The reader of ``collective_ms.search`` on synthetic readouts: the
collective operations' device time inside search chunks, per chunk and
chip, and None where a run holds none (one chip, an older program, or a
trace with no device)."""
import numpy as np
import pytest

from perfbench.harness import registry as reg
from perfbench.harness.capture import Span
from perfbench.harness.runner import Readout
from perfbench.harness.trace_reduce import DeviceTrace, merge


def _readout(spans, events, chips):
    busy = []
    for dev in range(chips):
        iv = np.array([(a, b) for d, _, a, b in events if d == dev])
        busy.append(merge(iv.reshape(-1, 2)))
    return Readout(cell=None, window=None, spans=list(spans),
                   device=DeviceTrace(busy, list(events)), peaks={})


def _read(readout):
    return reg.Registry().metric("collective_ms.search").read(readout)


CHUNKS = [Span("search.chunk", t, t + 0.05, {}) for t in (0.0, 1.0)]


def _chunk_events(dev, t):
    """One chunk on one chip: the kernel, the merge's two all-gathers (one
    as an async pair) and its top-k."""
    return [(dev, "%topk_scores_pallas.1 = (f32[256,276352]", t, t + 0.04),
            (dev, "%all-gather.10 = f32[4,10,256]", t + 0.040, t + 0.041),
            (dev, "%all-gather-start.2 = (s32[10,256]", t + 0.041,
             t + 0.0412),
            (dev, "%all-gather-done.2 = s32[4,10,256]", t + 0.0412,
             t + 0.0418),
            (dev, "%custom-call = (f32[256,10]", t + 0.042, t + 0.043)]


def test_reads_the_collectives_inside_chunks_per_chunk_and_chip():
    events = [ev for dev in range(4) for t in (0.0, 1.0)
              for ev in _chunk_events(dev, t)]
    # an all-reduce outside every chunk (set-up) is not counted
    events.append((0, "%all-reduce.3 = f32[768]", 0.5, 0.6))
    # each chunk: 1.0 + 0.2 + 0.6 = 1.8 ms of collectives on each chip
    assert _read(_readout(CHUNKS, events, 4)) == pytest.approx(1.8)


def test_returns_none_where_there_is_nothing_to_read():
    one_chip = [(0, "%topk_scores_pallas.1 = (f32[256,131072]", t,
                 t + 0.04) for t in (0.0, 1.0)]
    assert _read(_readout(CHUNKS, one_chip, 1)) is None  # no collective
    events = [ev for t in (0.0, 1.0) for ev in _chunk_events(0, t)]
    assert _read(_readout([], events, 1)) is None         # no chunk span
    assert _read(_readout(CHUNKS, [], 0)) is None         # no device
