"""lp_adjacency_s.sample — mean device-busy seconds per job of LP's
adjacency build (the edge list made symmetric, the ELL tables sorted into
place): inside each ``sampling.labels`` span, before the rounds' ``while``
loop starts (``harness/lp_loop.py``, device trace)."""
from perfbench.harness.lp_loop import loops


def read(r):
    found = loops(r)
    if not found:
        return None
    return sum(r.device.busy_s(span.start, a)
               for span, a, _ in found) / len(found)
