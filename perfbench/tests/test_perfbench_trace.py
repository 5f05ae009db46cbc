"""The reduction from a profiler trace to device busy time, and the
roofline arithmetic, on a small trace recorded on a v5e
(``record_trace.py``) and on hand-counted work."""
import json
import os

import numpy as np
import pytest

from perfbench.harness import roofline, trace_reduce as tr
from perfbench.work import label_prop, topk_scores

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small.json")) as f:
        clock = json.load(f)
    trace = tr.read_trace(os.path.join(DATA, "small.xplane.pb"),
                          clock["anchor_ns"])
    return trace, {k: v * 1e-9 for k, v in clock.items()
                   if k.endswith("_ns")}


def test_recorded_trace_has_one_device_with_operations(recorded):
    trace, clock = recorded
    assert len(trace.busy) == 1
    assert len(trace.events) >= 3
    names = " ".join(name for _, name, _, _ in trace.events)
    assert "topk" in names.lower() or "custom" in names.lower(), names


#: device timestamps in the trace lie up to about a millisecond early
#: against the host's clock (the first product of the recorded trace
#: shows 0.82 ms before the annotation that preceded its launch)
ALIGN_S = 2e-3


def test_device_work_lands_inside_the_host_window(recorded):
    trace, clock = recorded
    start, end = clock["anchor_ns"], clock["end_ns"]
    starts = np.array([s for _, _, s, _ in trace.events])
    ends = np.array([e for _, _, _, e in trace.events])
    assert starts.min() >= start - ALIGN_S and ends.max() <= end + ALIGN_S
    busy = trace.busy_s(start, end)
    assert 0 < busy <= end - start


def test_the_host_sleep_is_idle_on_the_device(recorded):
    trace, clock = recorded
    lo, hi = clock["sleep_start_ns"], clock["sleep_end_ns"]
    assert hi - lo >= 0.05
    assert trace.busy_s(lo + ALIGN_S, hi - ALIGN_S) == 0.0
    gaps = trace.gaps(clock["anchor_ns"], clock["end_ns"])
    assert (gaps[:, 1] - gaps[:, 0]).max() >= 0.045


def test_merge_covered_and_gaps():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0],
                   [8.0, 9.0]])
    merged = tr.merge(iv)
    np.testing.assert_array_equal(merged, [[0, 4], [5, 6], [8, 9]])
    assert tr.covered(merged, 1.0, 8.5) == pytest.approx(3 + 1 + 0.5)
    assert tr.covered(merged, 4.0, 5.0) == 0.0
    t = tr.DeviceTrace([merged], [])
    np.testing.assert_array_equal(t.gaps(0.0, 10.0),
                                  [[4, 5], [6, 8], [9, 10]])
    assert t.busy_in([(0, 1), (5, 5.5)]) == pytest.approx(1.5)


def test_idle_gaps_go_to_the_innermost_span():
    gaps = np.array([[1.0, 2.0], [5.0, 5.5], [20.0, 21.0]])
    spans = [("outer", 0.0, 10.0), ("inner", 0.5, 3.0)]
    got = tr.attribute(gaps, spans)
    assert got == {"inner": 1.0, "outer": 0.5, "outside spans": 1.0}


def test_topk_work_is_hand_counted():
    ops, nbytes = topk_scores.work(256, 1_048_576, 768)
    assert ops == 2 * 256 * 1_048_576 * 768 == 412_316_860_416
    assert nbytes == (1_048_576 * 768 + 256 * 768) * 4 == 3_222_011_904
    peaks = roofline.peaks_for("TPU v5 lite")
    # bound by bytes at 819 GB/s (3.93 ms), not by operations (2.09 ms)
    assert roofline.least_time_s(ops, nbytes, peaks, "f32") == \
        pytest.approx(3_222_011_904 / 819e9)


def test_label_prop_round_work_is_hand_counted():
    n, k = 8_841_823, 32
    ops, nbytes = label_prop.round_work(n, k)
    assert ops == 2 * n * k == 565_876_672
    assert nbytes == n * (k * 12 + 8) == 3_465_994_616
    peaks = roofline.peaks_for("TPU v5 lite")
    assert roofline.least_time_s(ops, nbytes, peaks, "f32") == \
        pytest.approx(3_465_994_616 / 819e9)


def test_peaks_are_keyed_by_device_kind():
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert roofline.ops_peak(peaks, "bf16") == 197e12
    assert roofline.ops_peak(peaks, "int8") == 393e12
    assert roofline.ops_peak(peaks, "f32") == 197e12
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks_for("TPU v9 imaginary")
