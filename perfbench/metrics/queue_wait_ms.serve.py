"""queue_wait_ms.serve — median milliseconds from a request's due time to
the start of the program's ``serve.tick`` span that served it (the tick
that last started before its answer; ticks run one at a time)."""
import numpy as np


def read(r):
    load = r.window.data.get("load")
    ticks = r.spans_named("serve.tick")
    if load is None or not ticks:
        return None
    starts = np.sort([s.start for s in ticks])
    done = load.done[load.ok]
    served_by = np.searchsorted(starts, done, side="right") - 1
    ok = served_by >= 0
    if not ok.any():
        return None
    wait = starts[served_by[ok]] - load.due[load.ok][ok]
    return 1e3 * float(np.median(wait))
