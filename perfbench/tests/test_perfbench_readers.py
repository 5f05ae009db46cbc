"""The per-layer readers added with the program's search spans and LP's
stages on the device trace, on synthetic readouts: each reads what it
should, and returns None where the run holds nothing to read (an older
program, or a trace with no device)."""
import numpy as np
import pytest

from perfbench.harness import registry as reg
from perfbench.harness.capture import Span
from perfbench.harness.runner import Readout
from perfbench.harness.trace_reduce import DeviceTrace, merge
from perfbench.tests.conftest import run_small


def _span(name, start, seconds, **attrs):
    return Span(name, start, start + seconds, attrs)


def _readout(spans, events=()):
    iv = np.array([(a, b) for _, _, a, b in events]).reshape(-1, 2)
    device = DeviceTrace([merge(iv)] if len(iv) else [], list(events))
    return Readout(cell=None, window=None, spans=list(spans), device=device,
                   peaks={})


def _read(name, readout):
    return reg.Registry().metric(name).read(readout)


def _rounds(start, seconds, n=5):
    """The loop's events as the TPU's trace shows them: one a round, each
    with its body's gather inside."""
    loop = "%while.11 = (s32[], s32[8841823], s32[5])"
    out = []
    for i in range(n):
        a = start + i * seconds
        out += [(0, loop, a, a + seconds),
                (0, "%fusion.5 = s32[282938336]", a + 0.1, a + seconds)]
    return out


#: two jobs' LP programs: adjacency ops, a short while loop of the build,
#: then the rounds' loop; and a while loop of another stage outside
JOBS = (
    [_span("sampling.graph", -4.0, 3.9),
     _span("sampling.labels", 0.0, 38.0, rounds=5),
     _span("sampling.labels", 40.0, 40.0, rounds=5)],
    [(0, "%while.3 = (s32[], f32[8])", -3.0, -2.0),
     (0, "%sort.16 = (s32[120705360]", 0.1, 1.5),
     (0, "%fusion.67 = s32[8841823]", 1.5, 3.2),
     (0, "%while.1 = (s32[], s32[4])", 3.2, 3.25),
     *_rounds(3.3, 6.9),
     (0, "%sort.16 = (s32[120705360]", 40.2, 43.5),
     *_rounds(43.5, 7.2)])


def test_lp_stages_are_read_around_the_rounds_loop():
    r = _readout(*JOBS)
    # busy before the loop: 3.1 s + the short loop's 0.05 s; then 3.3 s
    assert _read("lp_adjacency_s.sample", r) == pytest.approx(
        (3.15 + 3.3) / 2)
    assert _read("lp_round_ms.sample", r) == pytest.approx(
        1e3 * (6.9 + 7.2) / 2)


@pytest.mark.parametrize("name", ["lp_adjacency_s.sample",
                                  "lp_round_ms.sample"])
def test_lp_readers_return_none_without_a_loop_or_a_labels_span(name):
    spans, events = JOBS
    assert _read(name, _readout(spans)) is None             # no device
    assert _read(name, _readout(spans[:1], events)) is None  # no LP span
    assert _read(name, _readout(spans, events[:3])) is None  # no loop


#: reader -> (the span it reads, spans of a run, the value they give)
SPAN_CASES = {
    "upload_ms.search": (
        "search.upload", [_span("search.upload", t, d) for t, d in
                          [(0.0, 0.001), (1.0, 0.003), (2.0, 0.002)]]
        + [_span("search.readback", 0.5, 0.1)], 2.0),
    "readback_ms.search": (
        "search.readback", [_span("search.readback", t, d) for t, d in
                            [(0.0, 0.004), (1.0, 0.001), (2.0, 0.002),
                             (3.0, 0.009)]]
        + [_span("search.upload", 0.5, 0.1)], 3.0),
}


@pytest.mark.parametrize("name", sorted(SPAN_CASES))
def test_search_reader_reads_its_spans(name):
    _, spans, want = SPAN_CASES[name]
    assert _read(name, _readout(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SPAN_CASES))
def test_search_reader_returns_none_without_its_spans(name):
    own = SPAN_CASES[name][0]
    others = [s for _, spans, _ in SPAN_CASES.values() for s in spans
              if s.name != own] + JOBS[0]
    assert _read(name, _readout([])) is None
    assert _read(name, _readout(others)) is None


def test_traced_small_search_run_reports_upload_and_readback(small_root):
    line = run_small(small_root, "search.dense768.batch", traced=True)
    assert line["correct"] is True, line["checks"]
    for name in ("upload_ms.search", "readback_ms.search"):
        assert line["metrics"][name]["value"] > 0
