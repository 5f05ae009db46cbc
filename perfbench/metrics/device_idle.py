"""device_idle.<cells> — share of the traced window in which no operation
ran on the device (1 − busy / window, from the profiler trace), in
percent.  One reader for every ``device_idle.*`` name: each cell family's
name moves its own end-to-end metric."""


def read(r):
    return r.idle_percent()
