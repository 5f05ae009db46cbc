#!/usr/bin/env python3
"""Run one cell of WindTunnel's benchmark on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
named in BENCHMARK.json at the checkout's root and found by name under
``perfbench/`` (see ``perfbench/__init__.py``).  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device`` and, traced, ``breakdown``; ``checks``, the numbers
compared with the plain reference beside their limits, comes last and is
repeated on standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()   # set-up is timed from process start

import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from perfbench.harness.runner import main
    sys.exit(main(t0=T0))
