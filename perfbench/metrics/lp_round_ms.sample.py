"""lp_round_ms.sample — milliseconds of one label-propagation round: the
device time of the rounds' ``while`` loop inside each ``sampling.labels``
span, over the span's ``rounds``, averaged over jobs
(``harness/lp_loop.py``, device trace)."""
from perfbench.harness.lp_loop import loops


def read(r):
    found = loops(r)
    if not found:
        return None
    return 1e3 * sum(seconds / span.attrs["rounds"]
                     for span, _, seconds in found) / len(found)
