"""collective_ms.search — device milliseconds a search chunk spends in
collective operations: the events of the device trace whose HLO name is a
collective (all-gather, all-reduce, collective-permute, all-to-all,
reduce-scatter, with their ``-start``/``-done`` halves) and whose midpoints
fall inside the program's ``search.chunk`` spans, summed over the chips and
divided by their number and by the window's chunks.  Over four chips this
is the merge's all-gather of each chip's partial top-k lists
(``retrieval/sharded.py::_merge``, under the ``search.merge`` scope).
Returns None where the trace holds no such operation inside a chunk."""
import bisect
import re

COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter)")


def read(r):
    chunks = sorted((s.start, s.end) for s in r.spans_named("search.chunk"))
    chips = len(r.device.busy)
    if not chunks or not chips:
        return None
    starts = [a for a, _ in chunks]
    total, found = 0.0, False
    for _, name, a, b in r.device.events:
        if not COLLECTIVE.match(name):
            continue
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= chunks[i][1]:
            total += b - a
            found = True
    if not found:
        return None
    return 1e3 * total / chips / len(chunks)
