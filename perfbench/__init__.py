"""WindTunnel's on-chip benchmark (run with ``python3 perfbench/run.py``).

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it:

* ``configs/<config>.json`` — the deployment's sizes, and beside it
  ``configs/<config>_ref.py`` — its data, its plain reference and its
  control (``python3 perfbench/control.py`` runs that on the chip);
* ``traffic/<traffic>.json`` — the mix's parameters; its ``kind`` names
  the driver ``kinds/<kind>.py`` that runs it;
* ``metrics/<metric>.py`` — the reader of one per-layer metric, or
  ``metrics/<stem>.py`` for every ``<stem>.*`` metric that shares one;
* ``work/<kernel>.py`` — a kernel's operations and bytes from shapes;
* ``peaks.json`` — the chips' published peaks, by ``device_kind``;
* ``tests/sizes/{configs,traffic}/<name>.json`` — the values that put a
  configuration or a traffic mix at a size the CPU tests hold.
"""
