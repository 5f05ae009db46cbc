"""Experiment-grid evaluation CLI (DESIGN.md §8) — runs a declarative
(sampler × retrieval engine × k × metric) grid over the synthetic corpus
through the trie-shared plan executor and prints the sample-fidelity report.

  PYTHONPATH=src python -m repro.launch.evaluate --grid default
  PYTHONPATH=src python -m repro.launch.evaluate --grid smoke --json results/eval.json
  PYTHONPATH=src python -m repro.launch.evaluate --engines exact,lsh --ks 3,10,20
  PYTHONPATH=src python -m repro.launch.evaluate --grid smoke --backend pallas --sharded --mesh host
  PYTHONPATH=src python -m repro.launch.evaluate --grid smoke --streamed --mesh auto
  PYTHONPATH=src python -m repro.launch.evaluate --grid smoke --backend int8 --no-tuned-kernels
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os

from repro.data.synthetic import generate_corpus
from repro.eval import (GridSpec, SearchConfig, available_backends,
                        available_retrieval_engines, available_samplers,
                        backend_recall_curve, build_fidelity_report,
                        format_backend_curve, format_fidelity_report,
                        get_backend, get_retrieval_engine, get_sampler,
                        run_grid)
from repro.kernels import tuning
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.logs import (add_logging_args, add_obs_args, init_obs,
                               setup_logging, write_metrics)
from repro.launch.mesh import parse_mesh

log = logging.getLogger("repro.launch.evaluate")

GRIDS = {
    # 3 samplers x 4 engines x 2 ks x 4 metrics = 96 cells
    "default": GridSpec(),
    # minimal end-to-end check: 3 samplers x 2 engines x 1 k x 2 metrics
    "smoke": GridSpec(engines=("exact", "tfidf"), ks=(3,),
                      metrics=("precision", "mrr"), max_queries=128),
}


def _csv(s):
    return tuple(x for x in s.split(",") if x)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--grid", default="default", choices=sorted(GRIDS),
                   help="grid preset; axis flags below override it")
    p.add_argument("--samplers", default=None,
                   help="comma list from " + ",".join(available_samplers()))
    p.add_argument("--engines", default=None,
                   help="comma list from "
                        + ",".join(available_retrieval_engines()))
    p.add_argument("--ks", default=None, help="comma list of cutoffs")
    p.add_argument("--metrics", default=None,
                   help="comma list of precision,recall,ndcg,mrr")
    p.add_argument("--backend", default="jnp",
                   help="scoring backend for the search core "
                        "(retrieval/backends.py): "
                        + ",".join(available_backends()))
    p.add_argument("--sharded", action="store_true",
                   help="run index search mesh-partitioned through "
                        "retrieval/sharded.py")
    p.add_argument("--streamed", action="store_true",
                   help="shard each corpus from birth: stream it chunk-wise "
                        "into per-device buffers and build the index "
                        "shard-locally (retrieval/sharded.sharded_build; "
                        "implies --sharded)")
    p.add_argument("--stream-chunk", type=int, default=65536,
                   help="host->device streaming chunk rows for --streamed")
    p.add_argument("--mesh", default="host",
                   help="mesh for --sharded/--streamed: host (1-device, "
                        "production axis names) or auto (all local devices)")
    p.add_argument("--no-tuned-kernels", action="store_true",
                   help="CLI escape hatch: ignore the autotuned block table "
                        "(kernels/tuning.py) and use the hard-coded kernel "
                        "defaults (env equivalent: REPRO_TUNED_KERNELS=off)")
    p.add_argument("--no-backend-curve", action="store_true",
                   help="skip the backend recall-vs-speed curve appended to "
                        "the fidelity output")
    p.add_argument("--sample-frac", type=float, default=None)
    p.add_argument("--max-queries", type=int, default=None)
    p.add_argument("--queries", type=int, default=512,
                   help="synthetic corpus size (queries)")
    p.add_argument("--qrels-per-query", type=int, default=16)
    p.add_argument("--topics", type=int, default=48)
    p.add_argument("--aux-fraction", type=float, default=1.0)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default=None, metavar="PATH",
                   help="persist grid cells + fidelity report as JSON")
    add_logging_args(p)
    add_obs_args(p)
    args = p.parse_args(argv)
    setup_logging(args)
    init_obs(args)
    enable_compile_cache()

    spec = GRIDS[args.grid]
    overrides = {}
    if args.samplers:
        overrides["samplers"] = _csv(args.samplers)
    if args.engines:
        overrides["engines"] = _csv(args.engines)
    if args.ks:
        overrides["ks"] = tuple(int(k) for k in _csv(args.ks))
    if args.metrics:
        overrides["metrics"] = _csv(args.metrics)
    if args.sample_frac is not None:
        overrides["sample_frac"] = args.sample_frac
    if args.max_queries is not None:
        overrides["max_queries"] = args.max_queries
    overrides["seed"] = args.seed
    spec = dataclasses.replace(spec, **overrides)

    # unknown sampler/engine/backend names fail here with the registry's
    # error message (the core/engines.py UX), before any corpus work —
    # the same error contract as launch/sample.py --strategy
    for name in spec.samplers:
        get_sampler(name)
    for name in spec.engines:
        get_retrieval_engine(name)
    get_backend(args.backend)
    if args.no_tuned_kernels:
        tuning.set_table(None)      # force hard-coded kernel defaults
    search = SearchConfig(backend=args.backend,
                          sharded=args.sharded or args.streamed,
                          streamed=args.streamed,
                          stream_chunk=args.stream_chunk,
                          mesh=(parse_mesh(args.mesh)
                                if args.sharded or args.streamed else None))

    corpus = generate_corpus(
        num_queries=args.queries, qrels_per_query=args.qrels_per_query,
        num_topics=args.topics, aux_fraction=args.aux_fraction,
        vocab_size=args.vocab, query_len=24, seed=args.seed)
    log.info("corpus: %d entities (%d judged), %d queries",
             corpus.num_entities, corpus.num_primary, corpus.num_queries)
    log.info("grid: %d samplers x %d engines x %d ks x %d metrics "
             "= %d cells (backend=%s, sharded=%s)",
             len(spec.samplers), len(spec.engines), len(spec.ks),
             len(spec.metrics), spec.num_cells, args.backend, args.sharded)

    result = run_grid(corpus, spec, search=search, verbose=True)

    log.info("\ncells (sampler, engine, k, metric -> value):")
    for (s, e, k, m), v in sorted(result.cells.items()):
        log.info("  %-11s %-8s k=%-3d %-10s %.4f", s, e, k, m, v)

    log.info("\nplan-trie stage counters (shared prefixes executed once):")
    log.info("%s", result.trie.summary())

    report = None
    if "full" in spec.samplers:
        report = build_fidelity_report(result.cells, spec)
        log.info("\n%s", format_fidelity_report(report, spec))
    else:
        log.info("\n(no 'full' sampler in the grid -> skipping the "
                 "fidelity report; add full to --samplers for deltas and "
                 "Kendall-tau)")

    curve = None
    if not args.no_backend_curve:
        # backend-level recall-vs-speed on the grid's own embedding: the
        # int8 backend's recall@10 vs jnp exact, swept over rerank_factor
        import jax.numpy as jnp
        from repro.eval import tfidf_embedder
        ev, qv = tfidf_embedder(corpus)
        nq = min(128, qv.shape[0])
        curve = backend_recall_curve(jnp.asarray(ev), jnp.asarray(qv[:nq]),
                                     k=10)
        log.info("\n%s", format_backend_curve(curve, k=10))

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        out = {"grid": result.to_json()}
        if report is not None:
            out["fidelity"] = report.to_json()
        if curve is not None:
            out["backend_curve"] = curve
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        log.info("\nwrote %s", args.json)
    metrics_path = write_metrics(
        args, {"plan": result.trie.metrics.snapshot()})
    if metrics_path:
        log.info("wrote %s", metrics_path)


if __name__ == "__main__":
    main()
