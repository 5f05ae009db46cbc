"""upload_ms.search — median milliseconds of the program's ``search.upload``
span: one chunk of queries copied from the host to the device (the span
blocks on the copy)."""
import numpy as np


def read(r):
    spans = r.spans_named("search.upload")
    if not spans:
        return None
    return 1e3 * float(np.median([s.end - s.start for s in spans]))
