import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Smoke tests and benchmarks never import this module.

"""Multi-pod dry-run: lower + compile EVERY (architecture x input-shape)
cell on the production meshes and extract the roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Success of ``.lower().compile()`` for the 16x16 (single-pod, 256-chip) and
2x16x16 (multi-pod, 512-chip) meshes is the deliverable; the per-cell
memory_analysis / cost_analysis / collective-bytes parse feeds
EXPERIMENTS.md §Dry-run and §Roofline.
"""
import argparse
import json
import logging
import re
import sys
import time
import traceback

import jax

from repro.configs import get_arch, iter_cells, list_archs
from repro.launch.cells import build_cell
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.logs import add_logging_args, setup_logging
from repro.launch.mesh import make_production_mesh
# the hardware constants live at the bottom of the stack (kernels/tuning.py)
# so the autotuner's roofline never imports upward into launch
from repro.kernels.tuning import HBM_BW, ICI_BW, PEAK_FLOPS_BF16  # noqa: F401

log = logging.getLogger("repro.launch.dryrun")

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*?\s*=\s*([a-z0-9_]+)\[([0-9,]*)\]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum OUTPUT operand bytes of every collective op in the (SPMD-
    partitioned, per-device) HLO. Returns {op_kind: bytes}."""
    out: dict = {}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(
            r".*=\s*(?:\(([^)]*)\)|([a-z0-9]+\[[0-9,]*\][^ ]*))\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)", s)
        if not m:
            continue
        shapes_str = m.group(1) or m.group(2)
        kind = m.group(3)
        total = 0
        for dt, dims in _SHAPE_RE.findall(shapes_str):
            nbytes = _DTYPE_BYTES.get(dt)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * nbytes
        out[kind] = out.get(kind, 0) + total
    return out


def run_cell(arch_id: str, shape_name: str, mesh, n_chips: int,
             verbose: bool = True) -> dict:
    t0 = time.time()
    cell = build_cell(arch_id, shape_name, mesh)
    with mesh:
        lowered = cell.fn.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    coll_total = float(sum(coll.values()))

    # NOTE on units: cost_analysis / collective parse are per-DEVICE numbers
    # (SPMD partitioned module). Roofline terms are therefore per device.
    res = {
        "arch": arch_id, "shape": shape_name, "n_chips": n_chips,
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "model_flops_per_step": cell.model_flops_per_step,
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes",
                                           None),
        },
        "roofline": {
            "compute_s": flops / PEAK_FLOPS_BF16,
            "memory_s": bytes_accessed / HBM_BW,
            "collective_s": coll_total / ICI_BW,
        },
    }
    r = res["roofline"]
    r["bottleneck"] = max(r, key=lambda k: r[k] if k.endswith("_s") else -1)
    total_useful = cell.model_flops_per_step / n_chips
    r["useful_flops_ratio"] = (total_useful / flops) if flops else 0.0
    if verbose:
        log.info("[%s x %s] ok (lower %.0fs compile %.0fs) "
                 "compute %.2fms memory %.2fms collective %.2fms -> %s",
                 arch_id, shape_name, t_lower, t_compile,
                 r["compute_s"] * 1e3, r["memory_s"] * 1e3,
                 r["collective_s"] * 1e3, r["bottleneck"])
        log.info("    temp %.2f GiB/device; args %.2f GiB/device",
                 (res["memory"]["temp_size"] or 0) / 2**30,
                 (res["memory"]["argument_size"] or 0) / 2**30)
    return res


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, choices=list_archs())
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    p.add_argument("--out", default=None, help="write JSON results here")
    add_logging_args(p)
    args = p.parse_args(argv)
    setup_logging(args)
    enable_compile_cache()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single-pod-16x16", make_production_mesh(), 256))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi-pod-2x16x16",
                       make_production_mesh(multi_pod=True), 512))

    cells = (list(iter_cells()) if args.all or not args.arch
             else [(args.arch, s) for s in
                   (get_arch(args.arch).shapes if not args.shape
                    else [args.shape])
                   if s not in get_arch(args.arch).skip_shapes])

    results = []
    failures = 0
    for mesh_name, mesh, n_chips in meshes:
        log.info("=== mesh %s (%d chips, %d devices visible) ===",
                 mesh_name, n_chips, len(jax.devices()))
        for arch_id, shape_name in cells:
            try:
                res = run_cell(arch_id, shape_name, mesh, n_chips)
            except Exception as e:
                failures += 1
                traceback.print_exc()
                res = {"arch": arch_id, "shape": shape_name, "ok": False,
                       "mesh": mesh_name, "error": repr(e)[:500]}
            res["mesh"] = mesh_name
            results.append(res)
            if args.out:
                with open(args.out + ".json", "w") as f:
                    json.dump(results, f, indent=2)
    log.info("\n%d/%d cells compiled OK", len(results) - failures,
             len(results))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
