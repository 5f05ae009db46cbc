"""Per-layer metric readers: ``metrics/<name>.py`` defines ``read(readout)``
(a ``harness.runner.Readout`` of the traced run), returning the metric's
value, or None where the run holds nothing to read."""
