"""graph_s.sample — mean seconds of the program's ``sampling.graph`` span
per job: Alg. 1 (tau quantile, per-query group-by, pair generation, edge
dedup).  The span blocks on its outputs, so it covers execution."""


def read(r):
    spans = r.spans_named("sampling.graph")
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans)
