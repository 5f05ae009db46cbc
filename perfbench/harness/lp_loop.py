"""Label propagation's stages inside the program's ``sampling.labels``
spans, from the device trace.  LP is one compiled program: the adjacency
build (symmetrize and the ELL sorts), then a ``while`` loop, the
``lax.scan`` over the rounds, which the TPU's trace shows as several
``while`` events a job.  The loop is the ``while`` operation with the most
device time inside the span, summed over its events; what the device ran
in the span before its first event is the adjacency build."""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

LOOP = re.compile(r"^%?while(\.\d+)?\b")


def loops(r) -> List[Tuple[object, float, float]]:
    """(span, start of the loop, device seconds of the loop) for each
    ``sampling.labels`` span in which device 0 ran the loop, on the host
    clock."""
    found = []
    for span in r.spans_named("sampling.labels"):
        ops: Dict[str, List[Tuple[float, float]]] = {}
        for dev, name, a, b in r.device.events:
            if (dev == 0 and LOOP.match(name)
                    and span.start <= 0.5 * (a + b) <= span.end):
                ops.setdefault(name.split(" ", 1)[0], []).append((a, b))
        if ops:
            events = max(ops.values(),
                         key=lambda ev: sum(b - a for a, b in ev))
            found.append((span, min(a for a, _ in events),
                          sum(b - a for a, b in events)))
    return found
