"""WindTunnel sampling CLI — the paper's end-to-end pipeline through the
sampling-core front door (DESIGN.md §10).

  PYTHONPATH=src python -m repro.launch.sample --queries 1280 --target-frac 0.15 \
      --out results/sample

  # size x seed sweep: graph build + label propagation run ONCE, every
  # (size, seed) draw reuses the cached labels (sizes <=1 are fractions
  # of the eligible universe, >1 absolute entity counts)
  PYTHONPATH=src python -m repro.launch.sample --sweep-sizes 0.05,0.1,0.15 \
      --sweep-seeds 0,1,2

  # baselines share the same session (and staged graph, when they need it)
  PYTHONPATH=src python -m repro.launch.sample --strategy degree_stratified

Generates (or loads) a corpus, stages GraphBuilder -> GraphSampler state in
a :class:`~repro.core.sampling_core.SamplerSession`, draws the sample(s),
reports community statistics and the Yule-Simon fit, and writes the sampled
qrel table + entity mask.
"""
from __future__ import annotations

import argparse
import json
import logging
import os

import jax.numpy as jnp
import numpy as np

from repro.core import (QRelTable, SamplerSession, SamplerSpec,
                        available_engines, available_samplers, fit_em,
                        get_sampler)
from repro.core.engines import get_engine
from repro.data.synthetic import generate_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.logs import (add_logging_args, add_obs_args, init_obs,
                               setup_logging, write_metrics)
from repro.launch.mesh import parse_mesh

log = logging.getLogger("repro.launch.sample")


def _csv_floats(s):
    return tuple(float(x) for x in s.split(",") if x)


def _csv_ints(s):
    return tuple(int(x) for x in s.split(",") if x)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--queries", type=int, default=1280)
    p.add_argument("--qrels-per-query", type=int, default=32)
    p.add_argument("--topics", type=int, default=96)
    p.add_argument("--aux-fraction", type=float, default=2.0)
    p.add_argument("--strategy", default="windtunnel",
                   help="sampling strategy from the registry "
                        "(core/samplers.py): " + ",".join(available_samplers()))
    p.add_argument("--target-frac", type=float, default=0.15)
    p.add_argument("--tau-quantile", type=float, default=0.5)
    p.add_argument("--fanout", type=int, default=16)
    p.add_argument("--lp-rounds", type=int, default=5)
    p.add_argument("--engine", default="sort",
                   help="label-prop engine from the registry "
                        "(core/engines.py): " + ",".join(available_engines()))
    p.add_argument("--sharded", action="store_true",
                   help="run the mesh-partitioned graph+LP stages "
                        "(core/sharded_pipeline.py; requires an ELL-family "
                        "engine)")
    p.add_argument("--streamed", action="store_true",
                   help="shard the qrel table from birth: route host-side, "
                        "stream per-shard buffers to their devices, and "
                        "build the graph shard-locally — no device ever "
                        "holds the global table (implies --sharded)")
    p.add_argument("--stream-chunk", type=int, default=65536,
                   help="host->device streaming chunk rows for --streamed")
    p.add_argument("--mesh", default="host", choices=["host", "auto"],
                   help="mesh for --sharded/--streamed: 1-device host mesh "
                        "or all local devices on the data axis")
    p.add_argument("--sweep-sizes", default=None, metavar="S1,S2,...",
                   help="comma list of target sizes (<=1: fraction of the "
                        "eligible universe; >1: entity count); runs "
                        "session.sweep against ONE staged graph+LP")
    p.add_argument("--sweep-seeds", default=None, metavar="R1,R2,...",
                   help="comma list of draw seeds for --sweep-sizes "
                        "(default: just --seed)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    add_logging_args(p)
    add_obs_args(p)
    args = p.parse_args(argv)
    setup_logging(args)
    init_obs(args)
    enable_compile_cache()
    # unknown names fail with the registry's error message before any
    # corpus work — the same error contract as launch/evaluate.py
    get_sampler(args.strategy)
    get_engine(args.engine)
    if (args.sharded or args.streamed) and args.engine == "sort":
        p.error("--sharded/--streamed require an ELL-family engine; "
                "pass --engine ell or --engine pallas")

    corpus = generate_corpus(
        num_queries=args.queries, qrels_per_query=args.qrels_per_query,
        num_topics=args.topics, aux_fraction=args.aux_fraction,
        seed=args.seed)
    log.info("corpus: %d entities (%d judged), %d queries",
             corpus.num_entities, corpus.num_primary, corpus.num_queries)

    qrels = QRelTable(*(jnp.asarray(x) for x in corpus.qrels))
    spec = SamplerSpec(
        strategy=args.strategy, engine=args.engine,
        tau_quantile=args.tau_quantile, fanout=args.fanout,
        lp_rounds=args.lp_rounds,
        target_size=args.target_frac * corpus.num_primary, seed=args.seed,
        sharded=args.sharded or args.streamed,
        streamed=args.streamed, stream_chunk=args.stream_chunk,
        mesh=(parse_mesh(args.mesh)
              if args.sharded or args.streamed else None))
    session = SamplerSession(qrels, num_queries=corpus.num_queries,
                             num_entities=corpus.num_entities, spec=spec)
    if args.sharded or args.streamed:
        log.info("%s graph+LP on mesh %s (engine=%s)",
                 "streamed shard-local" if args.streamed else "sharded",
                 dict(spec.mesh.shape), spec.engine)

    stats = {}
    if args.sweep_sizes:
        sizes = _csv_floats(args.sweep_sizes)
        seeds = (_csv_ints(args.sweep_seeds) if args.sweep_seeds
                 else (args.seed,))
        sweep = session.sweep(sizes, seeds)
        log.info("sweep: %d sizes x %d seeds (strategy=%s)",
                 len(sizes), len(seeds), sweep.strategy)
        for (size, seed), draw in sorted(sweep.draws.items()):
            mask = np.asarray(draw.entity_mask)
            log.info("  size=%-10g seed=%-3d -> %d entities, %d queries",
                     size, seed, int(mask.sum()),
                     int(draw.reconstructed.num_queries))
        log.info("session stage counters (graph+LP staged once per sweep):")
        log.info("%s", session.summary())
        stats["sweep"] = sweep.to_json()
        mask = np.asarray(sweep.draws[(sweep.sizes[0],
                                       sweep.seeds[0])].entity_mask)
        recon_valid = np.asarray(
            sweep.draws[(sweep.sizes[0], sweep.seeds[0])]
            .reconstructed.qrels.valid)
        labels = (np.asarray(session.labels()[0])
                  if get_sampler(args.strategy).needs_labels
                  else np.zeros(corpus.num_entities, np.int32))
    else:
        draw = session.draw()
        mask = np.asarray(draw.entity_mask)
        recon_valid = np.asarray(draw.reconstructed.qrels.valid)
        strat = get_sampler(args.strategy)
        labels = np.zeros(corpus.num_entities, np.int32)
        if strat.needs_graph:
            edges, degrees = session.graph()
            deg = np.asarray(degrees)
            fit = fit_em(jnp.asarray(deg[deg > 0]), max_iters=300)
            log.info("affinity graph: %d edges; degree-law gamma = %.3f "
                     "(se %.2e)", int(edges.num_valid), float(fit.gamma),
                     float(fit.stderr))
            stats["gamma"] = float(fit.gamma)
        if strat.needs_labels:
            labels_arr, changes = session.labels()
            labels = np.asarray(labels_arr)
            sizes_arr = np.asarray(draw.sample.community_sizes)
            n_comm = int((sizes_arr > 0).sum())
            log.info("%d communities; LP changes/round = %s", n_comm,
                     np.asarray(changes).tolist())
            stats["communities"] = n_comm
        log.info("sample[%s]: %d entities, %d associated queries",
                 args.strategy, int(mask.sum()),
                 int(draw.reconstructed.num_queries))

    stats["entities"] = int(mask.sum())
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        np.savez(os.path.join(args.out, "sample.npz"),
                 entity_mask=mask, labels=labels, qrel_valid=recon_valid)
        with open(os.path.join(args.out, "stats.json"), "w") as f:
            json.dump(stats, f, indent=2)
        log.info("wrote %s/sample.npz", args.out)
    metrics_path = write_metrics(
        args, {"session_stage_counts": {
            st: {"executions": ex, "requests": rq}
            for st, (ex, rq) in session.stage_counts().items()}})
    if metrics_path:
        log.info("wrote %s", metrics_path)


if __name__ == "__main__":
    main()
