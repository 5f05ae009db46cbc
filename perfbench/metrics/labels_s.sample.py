"""labels_s.sample — mean seconds of the program's ``sampling.labels`` span
per job: the ELL adjacency build and every label-propagation round.  The
span blocks on its outputs, so it covers execution."""


def read(r):
    spans = r.spans_named("sampling.labels")
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans)
