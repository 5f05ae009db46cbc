"""Serving-tier CLI (DESIGN.md §14) — drive a :class:`~repro.serve.engine.
SearchServer` (bounded queue -> microbatch scheduler -> per-tenant live
indexes) as a load generator or for a single query.

  # load-generate: 512 requests over 4 tenants, report throughput + p50/p99
  PYTHONPATH=src python -m repro.launch.serve --requests 512 --tenants 4 \
      --rate 2000 --out results/serve.json

  # one query against a warm single-tenant server
  PYTHONPATH=src python -m repro.launch.serve --single --k 5

  # live ingest mid-run: append documents every N requests
  PYTHONPATH=src python -m repro.launch.serve --append-every 128 \
      --append-rows 64 --compact-threshold 256

  # observe it: spans to a trace, metrics snapshot on exit
  PYTHONPATH=src python -m repro.launch.serve --trace results/trace.jsonl \
      --metrics-json results/metrics.json
  PYTHONPATH=src python -m repro.launch.trace results/trace.jsonl --filter serve.

Engine/backend/mesh names resolve through the same registries as every
other CLI, so an unknown name fails fast with the registry's message
(launch/sample.py error contract).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
from typing import Optional

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.logs import (add_logging_args, add_obs_args, init_obs,
                               setup_logging, write_metrics)
from repro.obs import recompile
from repro.launch.mesh import parse_mesh
from repro.retrieval.backends import get_backend
from repro.retrieval.engines import (available_retrieval_engines,
                                     get_retrieval_engine)
from repro.retrieval.search_core import SearchConfig
from repro.serve import (IngestConfig, LoadSpec, SchedulerConfig,
                         SearchServer, run_load)

log = logging.getLogger("repro.launch.serve")


def _tenant_corpus(tenant: str, *, docs: int, dim: int, seed: int):
    """Deterministic per-tenant synthetic corpus — the provider the
    TenantCache rebuilds evicted tenants from."""
    tid = int(tenant.rsplit("-", 1)[-1]) if "-" in tenant else 0
    rng = np.random.default_rng(seed * 100_003 + tid)
    return rng.normal(size=(docs, dim)).astype(np.float32)


def build_server(args) -> SearchServer:
    mesh = (parse_mesh(args.mesh)
            if args.sharded or args.streamed else None)
    config = SearchConfig(
        engine=args.engine, backend=args.backend,
        sharded=args.sharded or args.streamed, streamed=args.streamed,
        mesh=mesh,
        engine_opts=json.loads(args.engine_opts) if args.engine_opts
        else None)
    return SearchServer(
        lambda t: _tenant_corpus(t, docs=args.docs, dim=args.dim,
                                 seed=args.seed),
        config=config,
        scheduler=SchedulerConfig(max_queue=args.max_queue,
                                  max_batch=args.max_batch,
                                  k_max=max(args.k_max, args.k)),
        ingest=IngestConfig(append_cap=args.append_cap,
                            compact_threshold=args.compact_threshold),
        max_tenants=args.max_tenants)


def run_recompile_check(server, rng, *, dim: int, k: int,
                        n_ticks: int) -> dict:
    """The scheduler's steady-state contract, measured: warm every batch
    bucket once, mark the sentinel waterline, then drive ``n_ticks`` more
    ticks across the bucket set — any XLA compilation past the mark is a
    retrace leak (a shape escaped the bucket/k_max pinning)."""
    sched = server.scheduler
    buckets = sched.config.bucket_set()

    def _submit_fill(fill: int) -> None:
        for _ in range(fill):
            q = rng.normal(size=(dim,)).astype(np.float32)
            if server.submit(q, k=k, tenant="tenant-0") is None:
                raise RuntimeError("queue full during recompile check; "
                                   "raise --max-queue")

    for b in buckets:                    # warmup: one compile per bucket
        _submit_fill(b)
        sched.tick()
    recompile.mark()
    steady_ticks = 0
    for i in range(n_ticks):             # steady state: every shape warm
        _submit_fill(buckets[i % len(buckets)])
        if sched.tick():
            steady_ticks += 1
    return {"steady_ticks": steady_ticks,
            "steady_recompiles": recompile.since()}


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(
        description="load-generate against (or query) the serving tier")
    p.add_argument("--docs", type=int, default=4096,
                   help="synthetic corpus rows per tenant")
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--engine", default="exact",
                   help="retrieval engine (retrieval/engines.py): "
                        + ",".join(available_retrieval_engines()))
    p.add_argument("--backend", default="jnp",
                   help="scoring backend (retrieval/backends.py): "
                        "jnp, pallas, int8")
    p.add_argument("--engine-opts", default=None, metavar="JSON",
                   help='engine overrides, e.g. \'{"n_lists": 16}\'')
    p.add_argument("--sharded", action="store_true",
                   help="mesh-partitioned search (retrieval/sharded.py)")
    p.add_argument("--streamed", action="store_true",
                   help="shard each tenant's corpus from birth "
                        "(implies --sharded)")
    p.add_argument("--mesh", default="host", choices=["host", "auto"])
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--single", action="store_true",
                   help="submit ONE query, print scores/ids, exit")
    p.add_argument("--requests", type=int, default=256,
                   help="load-generator arrivals")
    p.add_argument("--rate", type=float, default=float("inf"),
                   help="offered load, requests/s (default: back-to-back)")
    p.add_argument("--tenants", type=int, default=1,
                   help="tenant count, arrivals round-robin")
    p.add_argument("--max-tenants", type=int, default=8,
                   help="tenant-cache capacity (LRU evicts past this)")
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--k-max", type=int, default=16,
                   help="fixed top-k width of every dispatched batch")
    p.add_argument("--append-every", type=int, default=0, metavar="N",
                   help="live-ingest --append-rows docs to tenant-0 every "
                        "N requests (0: no ingest)")
    p.add_argument("--append-rows", type=int, default=64)
    p.add_argument("--append-cap", type=int, default=256)
    p.add_argument("--compact-threshold", type=int, default=4096)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--recompile-check", type=int, default=0, metavar="N",
                   help="after the load: warm every scheduler bucket, mark "
                        "the recompile sentinel, run N more ticks and exit "
                        "1 on any steady-state XLA compilation")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="write the load report JSON to PATH")
    add_logging_args(p)
    add_obs_args(p)
    args = p.parse_args(argv)
    setup_logging(args)
    init_obs(args)
    enable_compile_cache()
    if args.recompile_check > 0:
        recompile.enable()
    # fail fast with the registry error messages, before any build
    get_retrieval_engine(args.engine)
    get_backend(args.backend)

    server = build_server(args)
    rng = np.random.default_rng(args.seed + 1)

    if args.single:
        q = rng.normal(size=(args.dim,)).astype(np.float32)
        req = server.submit(q, k=args.k, tenant="tenant-0")
        if req is None:
            log.error("queue full")
            return 1
        server.drain()
        scores, ids = req.result(timeout=0)
        log.info("top-%d ids:    %s", args.k, ids.tolist())
        log.info("top-%d scores: %s",
                 args.k, [round(float(s), 4) for s in scores])
        write_metrics(args)
        return 0

    queries = rng.normal(size=(min(args.requests, 512),
                               args.dim)).astype(np.float32)
    spec = LoadSpec(n_requests=args.requests, rate=args.rate,
                    tenants=args.tenants, k=args.k, seed=args.seed)
    log.info("load: %d requests @ %s req/s over %d tenant(s), "
             "max_batch=%d engine=%s backend=%s", spec.n_requests,
             "inf" if not np.isfinite(spec.rate) else f"{spec.rate:g}",
             spec.tenants, args.max_batch, args.engine, args.backend)

    if args.append_every > 0:
        # interleave ingest with load: append via a wrapped scheduler tick
        done = {"n": 0}
        base_tick = server.scheduler.tick

        def tick_with_ingest():
            n = base_tick()
            done["n"] += n
            if n and done["n"] % max(args.append_every, 1) < n:
                server.append("tenant-0", rng.normal(
                    size=(args.append_rows, args.dim)).astype(np.float32))
            return n

        server.scheduler.tick = tick_with_ingest

    report = run_load(server.scheduler, queries, spec)
    row = report.to_row()
    log.info("throughput %.1f req/s   p50 %.2f ms   p99 %.2f ms   "
             "(%d completed, %d rejected, mean batch %.1f)",
             report.throughput_rps, report.p50_s * 1e3, report.p99_s * 1e3,
             report.completed, report.rejected, report.mean_batch)
    steady = None
    if args.recompile_check > 0:
        steady = run_recompile_check(server, rng, dim=args.dim, k=args.k,
                                     n_ticks=args.recompile_check)
        row.update(steady)
        log.info("recompile check: %d steady ticks, %d recompilations "
                 "past the warmup mark (per key: %s)",
                 steady["steady_ticks"], steady["steady_recompiles"],
                 recompile.counts())
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(row, f, indent=2)
        log.info("wrote %s", args.out)
    metrics_path = write_metrics(args)
    if metrics_path:
        log.info("wrote %s", metrics_path)
    if steady is not None and steady["steady_recompiles"]:
        log.error("steady-state recompile: the scheduler's bucket/k_max "
                  "pinning leaked a shape")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
