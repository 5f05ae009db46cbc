"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

    set-up     the driver makes the cell's data from the seed, builds the
               program's objects and warms every shape the window uses;
               ``setup_s`` runs from process start to the end of this
    window     ``--seconds`` of the cell's traffic; compilations in it are
               counted (there should be none); with ``--trace 1`` the
               program's spans and a profiler trace are taken
    check      once the window has closed, the device's peak has been read
               and the program's state is freed, the cell's outputs are
               compared with the configuration's plain reference; each
               number compared is printed beside its limit
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from types import ModuleType
from typing import Any, Dict, List, Optional, TextIO

from perfbench.harness import registry as reg

RUN_DIR = os.path.join(reg.CHECKOUT, ".perfbench_run")
CACHE_DIR = os.path.join(reg.CHECKOUT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """What a driver is given: the cell's entry, files and seed."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    ref: ModuleType
    devices: List[Any]


@dataclasses.dataclass
class Window:
    """The measured window, on the host's ``perf_counter`` clock."""

    start: float
    end: float
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with the reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def passed(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Readout:
    """What a per-layer reader (``metrics/<name>.py``) is given."""

    cell: Cell
    window: Window
    spans: List[Any]          # capture.Span, program spans of the window
    device: Any               # trace_reduce.DeviceTrace
    peaks: Dict[str, Any]

    def spans_named(self, name: str) -> List[Any]:
        return [s for s in self.spans if s.name == name]

    def idle_percent(self) -> float:
        """Share of the window in which no operation ran on the device."""
        busy = self.device.busy_s(self.window.start, self.window.end)
        return 100.0 * (1.0 - busy / self.window.seconds)


def use_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compile cache at one fixed path in the checkout,
    for every program (the program's own entry points read the same
    variable); call before the first compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int) -> List[Any]:
    """The first ``chips`` TPU chips; :class:`NoChip` when there are none
    or too few.  The benchmark never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (it found {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices: List[Any]) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks, default=0)


def _reported(value: Optional[float], where: str) -> Optional[float]:
    if value is None:
        return None
    value = float(value)
    if not math.isfinite(value):
        raise ValueError(f"{where} read {value}")
    return value


def run_cell(bench: Dict[str, Any], registry: reg.Registry, workload: str,
             seed: int, seconds: float, traced: bool, devices: List[Any],
             t0: float, run_dir: str = RUN_DIR) -> Dict[str, Any]:
    """Run one cell and return its result line (as a dict, ``checks``
    last)."""
    import jax
    from repro.obs import recompile
    from perfbench.harness.capture import Capture
    spec = reg.find_cell(bench, workload)
    traffic = registry.traffic(spec["traffic"])
    cell = Cell(workload, int(spec["chips"]), registry.config(spec["config"]),
                traffic, seed, registry.reference(spec["config"]), devices)
    driver = registry.kind(traffic["kind"]).Driver(cell)
    driver.setup()
    setup_s = time.perf_counter() - t0

    recompile.enable()
    compiled_before = recompile.total()
    capture = Capture(run_dir) if traced else None
    if capture is not None:
        with capture:
            window = driver.window(seconds)
    else:
        window = driver.window(seconds)
    compiles = recompile.total() - compiled_before
    peak = memory_peak(devices)

    driver.release()
    gc.collect()
    t_check = time.perf_counter()
    checks = driver.check()
    check_s = time.perf_counter() - t_check

    dev = devices[0]
    device: Dict[str, Any] = {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices()),
                              "memory_peak_bytes": peak}
    line: Dict[str, Any] = {"correct": all(c.passed for c in checks),
                            "attempted": window.attempted,
                            "failed": window.failed}
    if capture is None:
        wanted = reg.cell_metrics(bench, workload, "end_to_end")
        values = {"setup_s": setup_s, **window.end_to_end}
        line["metrics"] = {
            m["name"]: {"value": _reported(values[m["name"]], m["name"]),
                        "unit": m["unit"]} for m in wanted}
        line["device"] = device
    else:
        from perfbench.harness.roofline import peaks_for
        from perfbench.harness.trace_reduce import attribute, top
        trace, spans = capture.read()
        readout = Readout(cell, window, spans, trace, peaks_for(
            dev.device_kind, os.path.join(registry.root, "peaks.json")))
        metrics = {}
        for m in reg.cell_metrics(bench, workload, "per_layer"):
            value = _reported(registry.metric(m["name"]).read(readout),
                              m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"] = trace.busy_s(window.start, window.end)
        device["window_s"] = window.seconds
        line["device"] = device
        gaps = trace.gaps(window.start, window.end)
        line["breakdown"] = {
            "device_ops": top(trace.op_seconds(window.start, window.end)),
            "idle_gaps": top(attribute(
                gaps, [(s.name, s.start, s.end) for s in spans]))}
    line["compiles_in_window"] = compiles
    line["notes"] = getattr(driver, "notes", {})
    line["phases_s"] = {"setup": setup_s, "window": window.seconds,
                        "check": check_s}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line


def main(argv: Optional[List[str]] = None, *, t0: float,
         out: TextIO = sys.stdout, err: TextIO = sys.stderr) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="perfbench/run.py",
        description="Run one cell of BENCHMARK.json on the chip and print "
                    "its result as the last line of standard output.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    bench = reg.load_benchmark()
    chips = int(reg.find_cell(bench, args.workload)["chips"])
    use_compile_cache()
    try:
        devices = accelerator(chips)
    except NoChip as e:
        print(f"perfbench: {e}", file=err, flush=True)
        return 3
    os.makedirs(RUN_DIR, exist_ok=True)
    try:
        line = run_cell(bench, reg.Registry(), args.workload, args.seed,
                        args.seconds, bool(args.trace), devices, t0)
    finally:
        shutil.rmtree(RUN_DIR, ignore_errors=True)
    print("perfbench: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in line["phases_s"].items())
        + f", {line['compiles_in_window']} compilations in the window",
        file=err)
    if line["notes"]:
        print(f"perfbench: notes {json.dumps(line['notes'])}", file=err)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0
