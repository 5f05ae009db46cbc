"""Record the small profiler trace that ``test_perfbench_trace.py`` reads.

    python3 perfbench/tests/record_trace.py <out_dir>

On a TPU: two matrix products and one top-k kernel call with a 50 ms host
sleep between them, under ``jax.profiler`` with the options the benchmark
uses.  Writes ``<out_dir>/small.xplane.pb`` and ``<out_dir>/small.json``,
the host clock readings (``time.perf_counter_ns``) taken at the anchor
annotation and around the sleep, so a test can check that the reduction
puts device work and host time on one clock.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(out_dir: str) -> int:
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    import jax
    import jax.numpy as jnp
    from repro.kernels.topk_scoring.ops import topk_scores
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    x = jnp.ones((2048, 2048), jnp.float32)
    mm = jax.jit(lambda a: a @ a)
    corpus = jax.random.normal(jax.random.PRNGKey(0), (4096, 128))
    queries = corpus[:16]
    jax.block_until_ready((mm(x), topk_scores(queries, corpus, k=10)))
    tmp = os.path.join(out_dir, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench.anchor"):
        anchor = time.perf_counter_ns()
    mm(x).block_until_ready()
    sleep0 = time.perf_counter_ns()
    time.sleep(0.05)
    sleep1 = time.perf_counter_ns()
    jax.block_until_ready(topk_scores(queries, corpus, k=10))
    mm(x).block_until_ready()
    end = time.perf_counter_ns()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "small.json"), "w") as f:
        json.dump({"anchor_ns": anchor, "sleep_start_ns": sleep0,
                   "sleep_end_ns": sleep1, "end_ns": end,
                   "device_kind": jax.devices()[0].device_kind}, f)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("      ", repr(ev.name), ev.start_ns, ev.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
