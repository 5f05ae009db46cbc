"""readback_ms.search — median milliseconds of the program's
``search.readback`` span: one chunk's top-k scores and ids copied from the
device to the host, after the ``search.chunk`` span has waited for them."""
import numpy as np


def read(r):
    spans = r.spans_named("search.readback")
    if not spans:
        return None
    return 1e3 * float(np.median([s.end - s.start for s in spans]))
