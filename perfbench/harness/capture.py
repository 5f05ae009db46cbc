"""The traced run's capture: the program's spans (``repro.obs.trace``) and
a ``jax.profiler`` trace of the window, both read back on the host's
``perf_counter`` clock once the window has closed."""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import time
from typing import Any, Dict, List

from perfbench.harness.trace_reduce import ANCHOR, DeviceTrace, read_trace


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span, on the host's ``perf_counter`` clock."""

    name: str
    start: float
    end: float
    attrs: Dict[str, Any]


class Capture:
    """``with Capture(dir):`` traces what runs inside; ``read()`` then
    returns the device trace and the spans, and deletes the files."""

    def __init__(self, run_dir: str):
        self.trace_dir = os.path.join(run_dir, "trace")
        self.span_path = os.path.join(run_dir, "spans.jsonl")
        self.anchor_ns = 0
        self.wall_minus_perf = 0.0

    def __enter__(self) -> "Capture":
        import jax
        from repro.obs import trace as spans
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        if os.path.exists(self.span_path):
            os.remove(self.span_path)
        spans.enable(self.span_path)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python calls would swamp the trace
        opts.host_tracer_level = 1       # annotations, not runtime detail
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.anchor_ns = time.perf_counter_ns()
        self.wall_minus_perf = time.time() - time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        from repro.obs import trace as spans
        try:
            jax.profiler.stop_trace()
        finally:
            spans.disable()
        return False

    def read(self) -> "tuple[DeviceTrace, List[Span]]":
        files = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.trace_dir}")
        device = read_trace(files[0], self.anchor_ns)
        records: List[Span] = []
        with open(self.span_path, encoding="utf-8") as f:
            for line in f:
                rec = json.loads(line)
                start = rec["t0"] - self.wall_minus_perf
                records.append(Span(rec["name"], start, start + rec["dur_s"],
                                    rec.get("attrs", {})))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.remove(self.span_path)
        return device, records
