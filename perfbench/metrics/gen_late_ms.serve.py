"""gen_late_ms.serve — 99th percentile of how late the load generator began
a send past its due time, in milliseconds (host clock).  A starved
generator would otherwise read as a fast server."""
import numpy as np


def read(r):
    load = r.window.data.get("load")
    if load is None or load.attempted == 0:
        return None
    return 1e3 * float(np.percentile(load.late_s(), 99))
