"""lp_roofline.sample — the least time ``lp_rounds`` rounds of label
propagation over (N nodes, K slots) could take at the chip's published
peaks (``work/label_prop.py``, ``peaks.json``), over the device's busy time
inside the program's ``sampling.labels`` spans, in percent.  That busy
time also holds the adjacency build, which counts against the rounds."""
from perfbench.harness.roofline import least_time_s
from perfbench.work import label_prop


def read(r):
    spans = r.spans_named("sampling.labels")
    busy = r.device.busy_in((s.start, s.end) for s in spans)
    if not spans or busy <= 0:
        return None
    c = r.cell.config
    ops, nbytes = label_prop.round_work(c["num_entities"], c["max_degree"])
    least = len(spans) * c["lp_rounds"] * least_time_s(
        ops, nbytes, r.peaks, label_prop.DTYPE)
    return 100.0 * least / busy
