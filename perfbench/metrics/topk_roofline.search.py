"""topk_roofline.search — the least time the window's top-k scoring could
take at the chip's published peaks (``work/topk_scores.py``, each chunk of
each pass against the whole corpus), over the device's busy time in the
window, in percent.  The pad copy and the merge of partial lists are in
the busy time and not in the work.  Over several chips, each scans its
share of the rows and the busy time is the chips' mean, so the work is
one chip's share."""
from perfbench.harness.roofline import least_time_s
from perfbench.work import topk_scores


def read(r):
    chunks = r.window.data.get("chunk_rows")
    busy = r.device.busy_s(r.window.start, r.window.end)
    if not chunks or busy <= 0:
        return None
    c = r.cell.config
    rows = c["rows"] / r.cell.chips
    per_pass = sum(least_time_s(*topk_scores.work(q, rows, c["dim"]),
                                r.peaks, topk_scores.DTYPE) for q in chunks)
    return 100.0 * r.window.data["passes"] * per_pass / busy
