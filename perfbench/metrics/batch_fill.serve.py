"""batch_fill.serve — requests served over padded bucket rows, over every
tick of the window, in percent, from the program's ``serve.batch`` spans
(their ``fill`` and ``bucket``)."""


def read(r):
    batches = r.spans_named("serve.batch")
    rows = sum(s.attrs["bucket"] for s in batches)
    if not rows:
        return None
    return 100.0 * sum(s.attrs["fill"] for s in batches) / rows
