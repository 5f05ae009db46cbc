import json
import os
import shutil
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if path not in sys.path:
        sys.path.insert(0, path)

BENCH = os.path.join(CHECKOUT, "perfbench")

#: the cells' configurations and traffic at sizes a CPU test holds; widths
#: of the sampler and the k, chunk and batch shapes keep their roles
SMALL = {
    "configs/msmarco-qrels-sampler.json": dict(
        num_queries=512, judgment_rows=2560,
        judgments_per_query={"1": 128, "4": 256, "11": 128}, num_topics=8,
        num_entities=4096, fanout=4, max_degree=8, lp_rounds=3),
    "configs/msmarco-dense768-shard.json": dict(rows=8192,
                                                checked_answers=32),
    "traffic/batch.json": dict(queries=300, query_chunk=128),
    "traffic/open.json": dict(rate_per_s=200, pool=300, max_batch=8),
}


def _edit(path, **values):
    with open(path) as f:
        data = json.load(f)
    data.update(values)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark's files with small configurations, and the
    v5e's peaks under the CPU's device kind so traced runs can reduce."""
    root = str(tmp_path / "perfbench")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for rel, values in SMALL.items():
        _edit(os.path.join(root, rel), **values)
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = peaks["TPU v5 lite"]
    with open(os.path.join(root, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    return root


#: cells whose drivers, traffic and readers are built and tested but which
#: BENCHMARK.json does not hold yet (PERF.md, Open questions, says why)
UNLISTED = {
    "workloads": [
        {"name": "sample.msmarco.draws", "config": "msmarco-qrels-sampler",
         "traffic": "draws", "chips": 1},
        {"name": "serve.dense768.open", "config": "msmarco-dense768-shard",
         "traffic": "open", "chips": 1}],
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
    The draws cell reports ``sample_s`` and the sampler's idle share."""
    from perfbench.harness import registry
    full = registry.load_benchmark()
    out = {**full, **{key: full[key] + UNLISTED[key]
                      for key in ("workloads", "end_to_end", "per_layer")}}
    for m in out["end_to_end"] + out["per_layer"]:
        if m["name"] in ("sample_s", "device_idle.sample"):
            m["workloads"] = m["workloads"] + ["sample.msmarco.draws"]
    return out


def run_small(root, workload, *, seed=2**33 + 5, seconds=1.0, traced=False):
    """One run of ``workload`` through the harness on the CPU (the look
    for a chip is skipped); the result line as a dict."""
    import time

    import jax
    from perfbench.harness import registry, runner
    return runner.run_cell(bench(),
                           registry.Registry(root), workload, seed, seconds,
                           traced, jax.devices()[:1], time.perf_counter(),
                           run_dir=os.path.join(os.path.dirname(root), "run"))
