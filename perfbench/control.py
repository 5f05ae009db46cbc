#!/usr/bin/env python3
"""The control of each configuration's comparison: the plain reference,
put in the program's place and computed in the step below the precision
the configuration states, must come out not correct.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3

For each seed it prints one JSON line with the cell's numbers compared and
their limits, read from the control's outputs at the cell's own size.  The
control of a configuration is the ``control(config, traffic, seed)``
function of its ``configs/<name>_ref.py``, found by name; its docstring
says which precision step it takes.

It needs a TPU; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Sequence

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control(root: str, workload: str, seed: int,
            devices: Sequence[Any]) -> Dict[str, object]:
    """The cell's numbers compared, read from its configuration's control
    on ``devices``, and which of them fail their limits."""
    from perfbench.harness import registry as reg
    r = reg.Registry(root)
    spec = reg.find_cell(reg.load_benchmark(), workload)
    cell = {"config": r.config(spec["config"]),
            "traffic": r.traffic(spec["traffic"])}
    numbers = r.reference(spec["config"]).control(
        cell["config"], cell["traffic"], seed, devices)
    limits = dict(cell["config"]["limits"])
    failed = {n: v for n, v in numbers.items()
              if n in limits and not v <= limits[n]}
    return {"workload": workload, "seed": seed, "numbers": numbers,
            "limits": limits, "control_fails": sorted(failed)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from perfbench.harness import registry as reg
    from perfbench.harness.runner import NoChip, accelerator, \
        use_compile_cache
    chips = int(reg.find_cell(reg.load_benchmark(), args.workload)["chips"])
    use_compile_cache()
    try:
        devices = accelerator(chips)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control(reg.BENCH_DIR, args.workload, seed,
                                 devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
