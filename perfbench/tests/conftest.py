import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if path not in sys.path:
        sys.path.insert(0, path)

BENCH = os.path.join(CHECKOUT, "perfbench")

#: the cells' configurations and traffic mixes at sizes a CPU test holds,
#: one file each, found by name: ``sizes/<folder>/<name>.json`` holds the
#: values that replace those of ``perfbench/<folder>/<name>.json``; widths
#: of the sampler and the k, chunk and batch shapes keep their roles
SIZES = os.path.join(BENCH, "tests", "sizes")
#: the benchmark's folders that size files resize, and the key by which a
#: cell names its file there
SIZED = {"configs": "config", "traffic": "traffic"}


class NoTestSize(LookupError):
    """A configuration or traffic mix of a cell has no size file, so a CPU
    test would run it at its full size."""


def missing_sizes(cells, sizes=SIZES):
    """``<folder>/<name>`` of each configuration and traffic mix that
    ``cells`` name and ``sizes`` has no file for."""
    return sorted({f"{folder}/{cell[key]}" for cell in cells
                   for folder, key in SIZED.items()
                   if not os.path.isfile(os.path.join(
                       sizes, folder, cell[key] + ".json"))})


def _edit(path, **values):
    with open(path) as f:
        data = json.load(f)
    data.update(values)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make_small_root(root, bench_dir=BENCH, sizes=SIZES):
    """A copy at ``root`` of the benchmark's files under ``bench_dir`` with
    every size file of ``sizes`` applied, and the v5e's peaks under the
    CPU's device kind so traced runs can reduce."""
    shutil.copytree(bench_dir, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for folder in SIZED:
        for path in sorted(glob.glob(os.path.join(sizes, folder, "*.json"))):
            with open(path) as f:
                values = json.load(f)
            _edit(os.path.join(root, folder, os.path.basename(path)),
                  **values)
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = peaks["TPU v5 lite"]
    with open(os.path.join(root, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    return root


@pytest.fixture
def small_root(tmp_path):
    """The benchmark's files at their test sizes."""
    return make_small_root(str(tmp_path / "perfbench"))


#: cells whose drivers, traffic and readers are built and tested but which
#: BENCHMARK.json does not hold yet (PERF.md, Open questions, says why)
UNLISTED = {
    "workloads": [
        {"name": "sample.msmarco.draws", "config": "msmarco-qrels-sampler",
         "traffic": "draws", "chips": 1},
        {"name": "serve.dense768.open", "config": "msmarco-dense768-shard",
         "traffic": "open", "chips": 1},
        # the harness's path across chips (a born row-sharded corpus, the
        # merge of each chip's partial top-k), on four CPU devices
        {"name": "search.dense768.mesh4", "config": "msmarco-dense768-shard",
         "traffic": "batch", "chips": 4}],
    "end_to_end": [
        {"name": "serve_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["serve.dense768.open"]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "serve_p99_ms",
         "workloads": ["serve.dense768.open"]}
        for name, unit, better, source, layer in [
            ("queue_wait_ms.serve", "ms", "lower", "program_span",
             "serving"),
            ("batch_fill.serve", "%", "higher", "program_span", "serving"),
            ("gen_late_ms.serve", "ms", "lower", "host_clock",
             "load generator"),
            ("device_idle.serve", "%", "lower", "device_trace", "device")]],
}


def bench():
    """BENCHMARK.json with the unlisted cells, which the tests cover too.
    The draws cell reports ``sample_s`` and the sampler's idle share; the
    four-device search cell what the one-chip search cell reports."""
    from perfbench.harness import registry
    full = registry.load_benchmark()
    out = {**full, **{key: full[key] + UNLISTED[key]
                      for key in ("workloads", "end_to_end", "per_layer")}}
    for m in out["end_to_end"] + out["per_layer"]:
        if m["name"] in ("sample_s", "device_idle.sample"):
            m["workloads"] = m["workloads"] + ["sample.msmarco.draws"]
        if "search.dense768.batch" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["search.dense768.mesh4"]
    return out


#: the child process's result line starts with this
RESULT = "run_small: "


def _run_here(root, workload, seed, seconds, traced, benchmark, devices):
    import time

    from perfbench.harness import registry, runner
    return runner.run_cell(benchmark, registry.Registry(root), workload,
                           seed, seconds, traced, devices,
                           time.perf_counter(),
                           run_dir=os.path.join(os.path.dirname(root), "run"))


def run_small(root, workload, *, seed=2**33 + 5, seconds=1.0, traced=False,
              benchmark=None, sizes=SIZES, plant=None):
    """One run of ``workload`` of ``benchmark`` (``bench()`` by default)
    through the harness on the CPU, the look for a chip skipped, on as many
    devices as the cell's ``chips``; the result line as a dict.

    A one-chip cell runs in this process.  A cell of more chips runs in a
    child process that sees that many CPU devices (this one sees one);
    there ``plant``, a ``"module:function"``, is called first, to plant a
    fault.  A cell whose configuration or traffic has no size file in
    ``sizes`` is refused before it runs (:class:`NoTestSize`)."""
    from perfbench.harness import registry
    benchmark = benchmark or bench()
    spec = registry.find_cell(benchmark, workload)
    missing = missing_sizes([spec], sizes)
    if missing:
        raise NoTestSize(f"{workload} has no test size for "
                         f"{', '.join(missing)}: add {sizes}/<folder>/"
                         "<name>.json")
    chips = int(spec["chips"])
    if chips == 1:
        if plant is not None:
            raise ValueError("a one-chip cell runs in this process; plant "
                             "its fault with monkeypatch")
        import jax
        return _run_here(root, workload, seed, seconds, traced, benchmark,
                         jax.devices()[:1])
    args = dict(root=root, workload=workload, seed=seed, seconds=seconds,
                traced=traced, benchmark=benchmark, plant=plant)
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count="
                         f"{chips}".strip(),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(CHECKOUT, "src"), CHECKOUT,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", "import sys\nfrom perfbench.tests.conftest "
         "import child_run\nchild_run(sys.argv[1])", json.dumps(args)],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith(RESULT)]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(f"{workload} on {chips} CPU devices exited "
                           f"{proc.returncode}:\n{proc.stderr[-6000:]}")
    return json.loads(lines[-1][len(RESULT):])


def child_run(args_json):
    """The child process of :func:`run_small`: plant the fault, run the
    cell on every device this process sees, print the result line."""
    import importlib

    import jax
    args = json.loads(args_json)
    if args["plant"]:
        module, name = args["plant"].split(":")
        getattr(importlib.import_module(module), name)()
    line = _run_here(args["root"], args["workload"], args["seed"],
                     args["seconds"], args["traced"], args["benchmark"],
                     jax.devices())
    print(RESULT + json.dumps(line), flush=True)
