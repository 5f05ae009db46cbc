#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate the server sustains
without a growing backlog, by a sweep of open-loop rates on the chip.

    python3 perfbench/knee.py --config msmarco-dense768-shard \\
        --traffic open --seed 7 --seconds 10 --rates 1000,1500,2000

It takes a configuration and a traffic file by name, so a serving cell can
be swept before it has an entry in BENCHMARK.json.

One set-up, then one open-loop run per rate, each printing a JSON line:
the rate offered and completed, the latency median and 99th percentile,
the median latency of the last quarter of arrivals against the first (a
backlog that grows makes it climb), and how late the generator ran.  A rate
is sustained when every request is answered, the answers keep up with the
arrivals and the last quarter's median is under twice the first's.  The
serving cell's traffic file fixes its rate at about four fifths of the
knee; the sweep is run once, when that rate is chosen, and is not part of
the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def summary(res, rate: float, seconds: float):
    import numpy as np
    lat = res.latency_s()
    q = max(lat.size // 4, 1)
    first = float(np.median(lat[:q]))
    last = float(np.median(lat[-q:]))
    span = res.end - res.start
    done = int(np.count_nonzero(res.ok))
    return {"offered_per_s": rate, "requests": res.attempted,
            "completed_per_s": done / span if span > 0 else 0.0,
            "unanswered": res.failed,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "first_quarter_p50_ms": 1e3 * first,
            "last_quarter_p50_ms": 1e3 * last,
            "late_p99_ms": 1e3 * float(np.percentile(res.late_s(), 99)),
            "sustained": bool(res.failed == 0 and last < 2 * first
                              and res.end - res.start < seconds * 1.1)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from perfbench.harness import registry as reg
    from perfbench.harness.runner import (Cell, NoChip, accelerator,
                                          use_compile_cache)
    use_compile_cache()
    try:
        devices = accelerator(1)
    except NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 3
    r = reg.Registry()
    traffic = r.traffic(args.traffic)
    cell = Cell(f"{args.config}.{args.traffic}", 1, r.config(args.config),
                traffic, args.seed, r.reference(args.config), devices)
    driver = r.kind(traffic["kind"]).Driver(cell)
    driver.setup()
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        res = driver.load(rate, args.seconds, args.seed + i)
        print(json.dumps(summary(res, rate, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
