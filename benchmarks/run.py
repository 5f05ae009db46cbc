"""Benchmark harness — one function per paper table/figure plus kernel
micro-benches and the roofline reader. Prints ``name,us_per_call,derived``
CSV rows (derived = the table's headline number).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig4,table1
  PYTHONPATH=src python -m benchmarks.run --only kernels --json results/bench
  PYTHONPATH=src python -m benchmarks.run --autotune --only retrieval --json results/bench

Timing and provenance come from the obs layer (repro.obs.timing,
DESIGN.md §12) so the benches, the autotuner, and traced production runs
all measure the same way.  REPRO_TRACE=<path> additionally streams span
records from the instrumented cores while the benches run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.obs.timing import provenance
from repro.obs.timing import timeit as _timeit

ROWS = []


def row(name, us, derived, **extra):
    """Record one bench row; ``extra`` keys become first-class JSON columns
    (e.g. ``peak_bytes_per_device`` on the streamed-build rows)."""
    ROWS.append((name, us, derived, extra))
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_meta() -> dict:
    """Host/device/backend/git provenance stamped into every BENCH_*.json —
    perf trajectories across machines are uninterpretable without it."""
    return {**provenance(), "smoke": SMOKE}


# ---------------------------------------------------------------------------
# Fig. 4: degree distribution + Yule-Simon EM fit (paper: gamma = 2.94)
# ---------------------------------------------------------------------------

def bench_fig4():
    from repro.core import QRelTable, fit_em
    from repro.core.graph_builder import build_affinity_graph, node_degrees
    from repro.data.synthetic import generate_qrels

    q, e, s, _, _, ne = generate_qrels(num_queries=20000, qrels_per_query=3,
                                       alpha=0.5, num_topics=64, seed=1)
    qr = QRelTable(jnp.asarray(q), jnp.asarray(e), jnp.asarray(s),
                   jnp.ones(len(q), bool))
    build = jax.jit(lambda t: build_affinity_graph(
        t, num_queries=20000, tau_quantile=0.5, fanout=8))
    us = _timeit(lambda: build(qr))
    edges = build(qr)
    deg = np.asarray(node_degrees(edges, ne))
    fit = fit_em(jnp.asarray(deg[deg > 0]), max_iters=500)
    row("fig4_graph_build", us, f"gamma={float(fit.gamma):.3f}")
    row("fig4_em_fit",
        _timeit(lambda: fit_em(jnp.asarray(deg[deg > 0]), max_iters=500)),
        f"stderr={float(fit.stderr):.2e}")


# ---------------------------------------------------------------------------
# Tables I & II: p@3 + query density, full vs uniform vs WindTunnel
# ---------------------------------------------------------------------------

def bench_table1_table2():
    from repro.core import QRelTable, WindTunnelConfig, run_windtunnel
    from repro.data.synthetic import generate_corpus
    from repro.retrieval.experiment import evaluate_sample
    from repro.retrieval.tfidf import tfidf_vectors

    corpus = generate_corpus(num_queries=1280, qrels_per_query=32,
                             num_topics=96, aux_fraction=2.0, seed=0,
                             query_len=24, vocab_size=3072)
    ev, df = tfidf_vectors(corpus.passage_tokens, corpus.vocab_size)
    qv, _ = tfidf_vectors(corpus.query_tokens, corpus.vocab_size)

    qrels = QRelTable(*(jnp.asarray(x) for x in corpus.qrels))
    cfg = WindTunnelConfig(tau_quantile=0.5, fanout=16, lp_rounds=5,
                           target_size=0.15 * corpus.num_primary, seed=0)
    wt_fn = jax.jit(lambda q: run_windtunnel(
        q, num_queries=corpus.num_queries,
        num_entities=corpus.num_entities, config=cfg))
    us_wt = _timeit(lambda: wt_fn(qrels).sample.entity_mask, n=1)
    res = wt_fn(qrels)
    wt_mask = np.asarray(res.sample.entity_mask)
    rate = wt_mask.sum() / corpus.num_primary
    rng = np.random.default_rng(7)
    uni = np.zeros(corpus.num_entities, bool)
    uni[:corpus.num_primary] = rng.random(corpus.num_primary) < rate

    out = {}
    for name, mask in [("full", None), ("uniform", uni),
                       ("windtunnel", wt_mask)]:
        out[name] = evaluate_sample(name, corpus, ev, qv, mask, seed=0,
                                    engine="exact", query_chunk=128,
                                    max_queries=768)
    row("table1_p_at_3(windtunnel_pipeline)", us_wt,
        "p@3 full=%.3f uniform=%.3f windtunnel=%.3f" %
        (out["full"].p_at_3, out["uniform"].p_at_3,
         out["windtunnel"].p_at_3))
    row("table2_query_density", 0.0,
        "rho_q uniform=%.3f windtunnel=%.3f ratio=%.2f" %
        (out["uniform"].rho_q, out["windtunnel"].rho_q,
         out["windtunnel"].rho_q / max(out["uniform"].rho_q, 1e-9)))
    # the trained-encoder run (slow path) is persisted by examples/
    if os.path.exists("results/table1.json"):
        with open("results/table1.json") as f:
            enc = json.load(f)
        row("table1_trained_encoder", 0.0,
            "p@3 full=%.3f uniform=%.3f windtunnel=%.3f" %
            (enc["full"]["p_at_3"], enc["uniform"]["p_at_3"],
             enc["windtunnel"]["p_at_3"]))


# ---------------------------------------------------------------------------
# Kernel micro-benches (CPU interpret mode: correctness-path timing only;
# the TPU roofline story lives in EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------

def bench_kernels():
    from repro.kernels.topk_scoring.ops import topk_scores
    from repro.kernels.topk_scoring.ref import topk_scores_ref
    from repro.kernels.label_prop.ops import label_prop_round_t
    from repro.core.graph_builder import EdgeList, symmetrize

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (64, 64))
    c = jax.random.normal(jax.random.PRNGKey(1), (8192, 64))
    row("kernel_topk_scoring(pallas)",
        _timeit(lambda: topk_scores(q, c, k=8)), "k=8 n=8192")
    row("kernel_topk_scoring(jnp-ref)",
        _timeit(lambda: topk_scores_ref(q, c, k=8)), "k=8 n=8192")

    n, kdeg = 4096, 16
    nbr = jax.random.randint(key, (kdeg, n), -1, n)     # slot-major ELL
    wgt = jnp.abs(jax.random.normal(key, (kdeg, n)))
    labels = jnp.arange(n, dtype=jnp.int32)
    row("kernel_label_prop(pallas)",
        _timeit(lambda: label_prop_round_t(labels, nbr, wgt)),
        f"n={n} K={kdeg}")

    # every registered LP engine, side-by-side on the same graph (the §Perf
    # trade for Alg. 2: sort's O(E log E) shuffle vs ELL's dense O(N K^2))
    from repro.core import engines as eng
    rng = np.random.default_rng(0)
    u = rng.integers(0, n, 4 * n).astype(np.int32)
    v = rng.integers(0, n, 4 * n).astype(np.int32)
    w = rng.random(4 * n).astype(np.float32)
    edges = EdgeList(jnp.asarray(u), jnp.asarray(v), jnp.asarray(w),
                     jnp.asarray(u != v))
    src, dst, ww, val = symmetrize(edges)
    for name in eng.available_engines():
        engine = eng.get_engine(name)
        f = jax.jit(lambda engine=engine: eng.run_engine(
            engine, src, dst, ww, val, num_nodes=n, max_degree=32,
            rounds=3).labels)
        row(f"labelprop_engine[{name}]", _timeit(f),
            f"E={4*n} rounds=3 K=32")


# ---------------------------------------------------------------------------
# Eval subsystem: retrieval-engine build/search across corpus sizes
# (rows = engine x corpus size; the grid runner's index/search stages)
# ---------------------------------------------------------------------------

def bench_eval():
    from repro.data.synthetic import generate_corpus
    from repro.eval.engines import (available_retrieval_engines,
                                    get_retrieval_engine)
    from repro.eval.runner import tfidf_embedder

    key = jax.random.PRNGKey(0)
    for nq in (128, 512):
        corpus = generate_corpus(num_queries=nq, qrels_per_query=8,
                                 num_topics=16, aux_fraction=0.5,
                                 vocab_size=1024, passage_len=32,
                                 query_len=12, seed=0, pad_multiple=256)
        ev, qv = tfidf_embedder(corpus)
        vecs = jnp.asarray(ev)
        queries = jnp.asarray(qv[:min(128, corpus.num_queries)])
        n = corpus.num_entities
        for name in available_retrieval_engines():
            eng = get_retrieval_engine(name)
            t0 = time.time()
            index = jax.block_until_ready(eng.build(key, vecs))
            us_build = (time.time() - t0) * 1e6
            us = _timeit(lambda: eng.search(index, queries, k=10))
            row(f"eval_search[{name}|N={n}]", us,
                f"build_us={us_build:.0f} Q={queries.shape[0]} k=10")


# ---------------------------------------------------------------------------
# Search core: engine x scoring backend x corpus size through SearchSession
# (the hot path of DESIGN.md §9 — what both the grid and serving run)
# ---------------------------------------------------------------------------

def bench_retrieval():
    from repro.eval.fidelity import backend_recall_curve
    from repro.kernels import tuning
    from repro.retrieval.backends import available_backends
    from repro.retrieval.engines import available_retrieval_engines
    from repro.retrieval.search_core import SearchConfig, SearchSession

    d, q_n, k = 64, 64, 10
    sizes = (1024,) if SMOKE else (1024, 4096, 16384)
    engines = (("exact", "lsh") if SMOKE
               else available_retrieval_engines())
    queries = jax.random.normal(jax.random.PRNGKey(1), (q_n, d))
    us_by = {}                         # (engine, backend, n) -> us
    for n in sizes:
        vecs = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        for engine in engines:
            for backend in available_backends():
                t0 = time.time()
                session = SearchSession(
                    vecs, SearchConfig(engine=engine, backend=backend),
                    key=jax.random.PRNGKey(0))
                jax.block_until_ready(session.index)
                us_build = (time.time() - t0) * 1e6
                us = _timeit(lambda: session.search(queries, k=k))
                us_by[(engine, backend, n)] = us
                row(f"retrieval[{engine}|{backend}|N={n}]", us,
                    f"build_us={us_build:.0f} Q={q_n} k={k}")

    # int8-vs-f32 speedup column per engine x size (same SearchSession rows)
    for n in sizes:
        for engine in engines:
            f32 = us_by[(engine, "jnp", n)]
            i8 = us_by[(engine, "int8", n)]
            row(f"retrieval_int8_vs_f32[{engine}|N={n}]", i8,
                f"f32_us={f32:.1f} speedup={f32 / max(i8, 1e-9):.2f}x")

    # tuned-vs-default speedup column per kernel primitive x size: explicit
    # default blocks vs the autotuner table's resolution (explicit kwargs on
    # both sides, so stale jit caches can't blur the comparison)
    from repro.kernels.lsh_hamming.ops import hamming_topk_t
    from repro.kernels.topk_scoring.ops import topk_scores, topk_scores_int8
    from repro.retrieval.lsh import build_lsh, encode
    for n in sizes:
        vecs = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        lsh = build_lsh(jax.random.PRNGKey(0), vecs, n_bits=128)
        qcodes = encode(lsh.proj, queries)
        q8 = jnp.clip(jnp.round(queries * 10), -127, 127).astype(jnp.int8)
        c8 = jnp.clip(jnp.round(vecs * 10), -127, 127).astype(jnp.int8)
        cases = {
            ("topk", "float32"):
                lambda blk: topk_scores(queries, vecs, k=k, **blk),
            ("topk", "int8"):
                lambda blk: topk_scores_int8(q8, c8, k=k, **blk),
            ("hamming_topk", "int32"):
                lambda blk: hamming_topk_t(qcodes, lsh.codes, k=k, **blk),
        }
        for (kernel, dt), fn in cases.items():
            default = dict(tuning.DEFAULTS[kernel])
            tuned = tuning.resolve(kernel, n=n, dtype=dt)
            us_def = _timeit(lambda: fn(default))
            us_tun = _timeit(lambda: fn(tuned))
            row(f"retrieval_tuned_vs_default[{kernel}|{dt}|N={n}]", us_tun,
                f"default_us={us_def:.1f} tuned={tuned} "
                f"speedup={us_def / max(us_tun, 1e-9):.2f}x")

    # int8 recall-vs-speed curve at the largest size (recall@k vs jnp exact)
    n = sizes[-1]
    vecs = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    for r in backend_recall_curve(vecs, queries, k=k,
                                  rerank_factors=(1, 2, 4, 8)):
        rf = "-" if r["rerank_factor"] is None else r["rerank_factor"]
        row(f"retrieval_recall[{r['backend']}|rf={rf}|N={n}]",
            r["us_per_call"], f"recall@{k}={r['recall_at_k']:.4f}")

    # streamed shard-local build at 10x the largest global size above:
    # weak scaling, per-shard rows constant as the shard count grows
    _streamed_rows("retrieval", per_shard=2048 if SMOKE else 163840)


# ---------------------------------------------------------------------------
# Streamed shard-local build (DESIGN.md §13): weak-scaling rows — the
# per-shard size is held constant while the shard count (and hence total
# corpus) grows, so the per-device peak should stay flat.  Every point runs
# in this process on a mesh over the first ``shards`` devices present (a
# CPU run gets several with XLA_FLAGS=--xla_force_host_platform_device_
# count=<n>).  A high-water mark never resets within a process, so each
# point reports what its build left resident: the bytes held per device
# after the build less those held before it.
# ---------------------------------------------------------------------------

def _streamed_point(kind: str, per_shard: int, shards: int,
                    chunk: int = 65536) -> dict:
    """Build one streamed session over ``shards`` devices and time it."""
    import gc

    from repro.launch.mesh import make_mesh
    from repro.obs.memory import resident_bytes_per_device
    mesh = make_mesh((shards,), ("data",), devices=jax.devices()[:shards])
    before = resident_bytes_per_device()
    out = {"kind": kind, "per_shard": per_shard, "shards": shards}
    if kind == "retrieval":
        from repro.retrieval.search_core import SearchConfig, SearchSession
        d, q_n, k = 64, 64, 10
        n = per_shard * shards
        rng = np.random.default_rng(0)
        vecs = rng.standard_normal((n, d)).astype(np.float32)
        queries = jnp.asarray(
            rng.standard_normal((q_n, d)).astype(np.float32))
        t0 = time.time()
        session = SearchSession(
            vecs, SearchConfig(engine="exact", backend="jnp",
                               streamed=True, mesh=mesh, stream_chunk=chunk),
            key=jax.random.PRNGKey(0))
        jax.block_until_ready(session.index)
        out["build_us"] = (time.time() - t0) * 1e6
        out["search_us"] = _timeit(lambda: session.search(queries, k=k))
        out["n"] = n
    else:
        from repro.core import QRelTable
        from repro.core import sampling_core as sc
        from repro.data.synthetic import generate_corpus
        nq = per_shard * shards
        corpus = generate_corpus(num_queries=nq, qrels_per_query=16,
                                 num_topics=32, aux_fraction=1.0, seed=0,
                                 vocab_size=1024)
        qrels = QRelTable(*(np.asarray(x) for x in corpus.qrels))
        session = sc.SamplerSession(
            qrels, num_queries=corpus.num_queries,
            num_entities=corpus.num_entities,
            spec=sc.SamplerSpec(engine="ell", streamed=True, mesh=mesh,
                                stream_chunk=chunk,
                                target_size=0.15 * corpus.num_primary,
                                seed=0))
        t0 = time.time()
        session.labels()                    # stage shard-local graph + LP
        out["build_us"] = (time.time() - t0) * 1e6
        out["draw_us"] = _timeit(lambda: session.draw(seed=1).entity_mask,
                                 n=1)
        out["n"] = corpus.num_entities
        out["nq"] = nq
    after = resident_bytes_per_device()
    out["peak_bytes_per_device"] = max(after[d] - before[d] for d in after)
    del session
    gc.collect()
    return out


def _streamed_rows(kind: str, per_shard: int) -> None:
    n_dev = len(jax.devices())
    shard_counts = [s for s in (1, 2, 4, 8) if s <= n_dev]
    peaks = {}
    for shards in shard_counts:
        r = _streamed_point(kind, per_shard, shards)
        peaks[shards] = r["peak_bytes_per_device"]
        work_us = r.get("search_us", r.get("draw_us", 0.0))
        tag = (f"{kind}_streamed[exact|jnp|N={r['n']}|shards={shards}]"
               if kind == "retrieval" else
               f"{kind}_streamed[ell|nq={r['nq']}|shards={shards}]")
        row(tag, r["build_us"],
            f"work_us={work_us:.0f} per_shard={per_shard} "
            f"peak_bytes_per_device={r['peak_bytes_per_device']}",
            peak_bytes_per_device=r["peak_bytes_per_device"],
            shards=shards, per_shard=per_shard)
    base = max(peaks[shard_counts[0]], 1)
    worst = max(peaks[s] / base for s in shard_counts)
    row(f"{kind}_streamed_peak_flat", 0.0,
        " ".join(f"s{s}={peaks[s]}" for s in shard_counts) +
        f" worst_ratio={worst:.2f} (weak scaling: flat per-device peak)",
        peak_ratio=worst)


# ---------------------------------------------------------------------------
# Sampling core: staged graph-build / LP / per-draw timings per LP engine,
# and the sweep-reuse speedup of SamplerSession (DESIGN.md §10) — the
# draws-per-second win of cached labels vs the one-shot legacy entry point
# ---------------------------------------------------------------------------

def bench_sampling():
    import itertools

    from repro.core import QRelTable, WindTunnelConfig, run_windtunnel
    from repro.core import engines as eng
    from repro.core import label_prop as lp
    from repro.core import sampling_core as sc
    from repro.data.synthetic import generate_corpus

    nq = 256 if SMOKE else 1280
    corpus = generate_corpus(num_queries=nq, qrels_per_query=16,
                             num_topics=32, aux_fraction=1.0, seed=0,
                             vocab_size=1024)
    qrels = QRelTable(*(jnp.asarray(x) for x in corpus.qrels))
    n_ent, n_q = corpus.num_entities, corpus.num_queries
    target = 0.15 * corpus.num_primary
    engines = ("sort", "ell") if SMOKE else eng.available_engines()

    us_graph = _timeit(lambda: sc._graph_stage(
        qrels, num_queries=n_q, num_entities=n_ent, tau_quantile=0.5,
        fanout=16))
    row("sampling_graph_build", us_graph, f"N={n_ent} Q={n_q}")
    edges, _ = sc._graph_stage(qrels, num_queries=n_q, num_entities=n_ent,
                               tau_quantile=0.5, fanout=16)
    node_cap, edge_cap = lp.lp_caps(rows=qrels.entity_ids.shape[0],
                                    fanout=16, num_nodes=n_ent,
                                    edge_slots=edges.u.shape[0])
    for name in engines:
        us_lp = _timeit(lambda name=name: sc._labels_stage(
            edges, engine=name, num_entities=n_ent, max_degree=32,
            rounds=5, node_cap=node_cap, edge_cap=edge_cap))
        row(f"sampling_lp[{name}]", us_lp, f"N={n_ent} rounds=5 K=32")

    for name in engines:
        session = sc.SamplerSession(
            qrels, num_queries=n_q, num_entities=n_ent,
            spec=sc.SamplerSpec(engine=name, target_size=target, seed=0))
        session.labels()                    # stage graph + LP up front
        seeds = itertools.count()
        us_draw = _timeit(
            lambda: session.draw(seed=next(seeds)).entity_mask)
        row(f"sampling_draw[{name}]", us_draw,
            f"target={target:.0f} cached_labels=True")

    # sweep-reuse speedup: K draws against one staged session vs K one-shot
    # run_windtunnel calls (each re-paying graph build + LP)
    k_draws = 4 if SMOKE else 8
    cfg = WindTunnelConfig(target_size=target, seed=0, engine="ell")
    session = sc.SamplerSession(qrels, num_queries=n_q, num_entities=n_ent,
                                spec=sc.SamplerSpec.from_config(cfg))
    session.labels()
    seeds = itertools.count()

    def cached_draws():
        return [session.draw(seed=next(seeds)).entity_mask
                for _ in range(k_draws)]

    us_cached = _timeit(cached_draws, n=1)
    wt_fn = jax.jit(lambda q: run_windtunnel(
        q, num_queries=n_q, num_entities=n_ent,
        config=cfg).sample.entity_mask)
    us_full = _timeit(lambda: [wt_fn(qrels) for _ in range(k_draws)], n=1)
    dps_cached = k_draws / (us_cached / 1e6)
    dps_full = k_draws / (us_full / 1e6)
    row("sampling_sweep_reuse", us_cached,
        f"draws_per_s cached={dps_cached:.1f} full={dps_full:.1f} "
        f"speedup={dps_cached / max(dps_full, 1e-9):.2f}x")

    # streamed shard-local graph build at 10x the nq above: weak scaling,
    # per-shard queries constant as the shard count grows
    _streamed_rows("sampling", per_shard=320 if SMOKE else 12800)


# ---------------------------------------------------------------------------
# Serving tier (DESIGN.md §14): load-generator rows — throughput + p50/p99
# vs offered load, microbatch size and tenant count, plus the headline
# microbatched-vs-serial throughput ratio.  Latencies come off each
# request's completion future (the serve.request_latency_s data), so the
# bench measures exactly what the scheduler observes.
# ---------------------------------------------------------------------------

def bench_serve():
    from repro.retrieval.search_core import SearchConfig
    from repro.serve import (IngestConfig, LoadSpec, SchedulerConfig,
                             SearchServer, run_load)

    docs = 2048 if SMOKE else 16384
    d = 64
    n_req = 64 if SMOKE else 512
    rng = np.random.default_rng(0)
    corpora = {}

    def provider(tenant):
        if tenant not in corpora:
            corpora[tenant] = rng.normal(size=(docs, d)).astype(np.float32)
        return corpora[tenant]

    queries = rng.normal(size=(min(n_req, 256), d)).astype(np.float32)

    def make_server(max_batch, tenants):
        server = SearchServer(
            provider, config=SearchConfig(engine="exact", backend="jnp"),
            scheduler=SchedulerConfig(max_queue=max(n_req, 256),
                                      max_batch=max_batch, k_max=16),
            ingest=IngestConfig(compact_threshold=10 ** 9),
            max_tenants=max(tenants, 8))
        # warm every bucket shape so the rows measure steady state, not
        # the one-off XLA compiles the bucket set exists to amortise
        for t in range(tenants):
            for b in server.scheduler.config.bucket_set():
                for i in range(b):
                    server.submit(queries[i % queries.shape[0]],
                                  tenant=f"tenant-{t}")
                server.tick()
        server.drain()
        return server

    def load_row(tag, max_batch, tenants, rate):
        server = make_server(max_batch, tenants)
        rep = run_load(server.scheduler, queries,
                       LoadSpec(n_requests=n_req, rate=rate,
                                tenants=tenants, k=10))
        rate_s = "inf" if not np.isfinite(rate) else f"{rate:g}"
        row(f"serve_load[{tag}|rate={rate_s}|batch={max_batch}"
            f"|tenants={tenants}]",
            rep.p50_s * 1e6,
            f"thr={rep.throughput_rps:.1f}rps p99={rep.p99_s * 1e3:.2f}ms "
            f"mean_batch={rep.mean_batch:.1f}",
            throughput_rps=rep.throughput_rps, p50_s=rep.p50_s,
            p99_s=rep.p99_s, offered_rate=(None if not np.isfinite(rate)
                                           else rate),
            max_batch=max_batch, tenants=tenants,
            completed=rep.completed, rejected=rep.rejected)
        return rep

    # offered-load sweep at the full microbatch
    batched = None
    for rate in ((float("inf"),) if SMOKE
                 else (500.0, 2000.0, float("inf"))):
        rep = load_row("load_sweep", 32, 1, rate)
        if not np.isfinite(rate):
            batched = rep
    # microbatch-size sweep (batch=1 is the serial baseline: one search
    # dispatch per request, the pre-scheduler serving path)
    serial = None
    for mb in ((1, 8) if SMOKE else (1, 4, 8, 32)):
        rep = load_row("batch_sweep", mb, 1, float("inf"))
        if mb == 1:
            serial = rep
        if SMOKE and mb == 8:
            batched = rep
    # tenant-count sweep (per-tenant sessions via the TenantCache)
    for tenants in ((2,) if SMOKE else (2, 4)):
        load_row("tenant_sweep", 32, tenants, float("inf"))

    ratio = batched.throughput_rps / max(serial.throughput_rps, 1e-9)
    row("serve_microbatch_speedup", 0.0,
        f"serial={serial.throughput_rps:.1f}rps "
        f"batched={batched.throughput_rps:.1f}rps ratio={ratio:.2f}x",
        ratio=ratio, serial_rps=serial.throughput_rps,
        batched_rps=batched.throughput_rps)


# ---------------------------------------------------------------------------
# Roofline table from the dry-run artifacts (EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------

def bench_roofline(path="results/dryrun.json"):
    if not os.path.exists(path):
        row("roofline", 0.0, f"missing {path}; run repro.launch.dryrun first")
        return
    with open(path) as f:
        cells = json.load(f)
    ok = [c for c in cells if c.get("ok")]
    n_bottleneck = {}
    for c in ok:
        if c["mesh"] != "single-pod-16x16":
            continue
        r = c["roofline"]
        bot = r["bottleneck"].replace("_s", "")
        n_bottleneck[bot] = n_bottleneck.get(bot, 0) + 1
        row(f"roofline[{c['arch']}x{c['shape']}]",
            max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            f"bottleneck={bot} compute={r['compute_s']*1e3:.2f}ms "
            f"memory={r['memory_s']*1e3:.2f}ms "
            f"collective={r['collective_s']*1e3:.2f}ms")
    row("roofline_summary", 0.0,
        " ".join(f"{k}:{v}" for k, v in sorted(n_bottleneck.items())))


BENCHES = {
    "fig4": bench_fig4,
    "table1": bench_table1_table2,
    "kernels": bench_kernels,
    "eval": bench_eval,
    "retrieval": bench_retrieval,
    "sampling": bench_sampling,
    "serve": bench_serve,
    "roofline": bench_roofline,
}

SMOKE = False


def run_autotune() -> None:
    """Regenerate results/tuned_kernels.json and activate it for the
    benches that follow (the README 'make it fast' entry point).  Smoke
    mode tunes a reduced cell set so CI stays fast."""
    from repro.kernels import tuning
    if SMOKE:
        table = tuning.autotune(buckets=("le1024", "le4096"), max_evals=4,
                                wall_iters=0)
    else:
        table = tuning.autotune(max_evals=12, wall_iters=1)
    row("autotune", 0.0,
        f"entries={len(table.entries)} -> {tuning.RESULTS_TABLE_PATH}")


def main() -> None:
    global SMOKE
    p = argparse.ArgumentParser()
    p.add_argument("--only", "--section", dest="only", default=None,
                   help="comma-separated subset of " + ",".join(BENCHES))
    p.add_argument("--smoke", action="store_true",
                   help="reduced sweep (CI: smallest corpus, 2 engines)")
    p.add_argument("--autotune", action="store_true",
                   help="regenerate results/tuned_kernels.json with the "
                        "kernel autotuner (kernels/tuning.py) before "
                        "running the benches, and bench with it active")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="directory to persist each section's rows as "
                        "BENCH_<name>.json (the perf trajectory record)")
    args = p.parse_args()
    SMOKE = args.smoke
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    if args.autotune:
        run_autotune()
    meta = bench_meta()
    for n in names:
        start = len(ROWS)
        BENCHES[n]()
        if args.json:
            os.makedirs(args.json, exist_ok=True)
            out = os.path.join(args.json, f"BENCH_{n}.json")
            with open(out, "w") as f:
                json.dump({"meta": meta,
                           "rows": [{"name": r[0], "us_per_call": r[1],
                                     "derived": r[2], **r[3]}
                                    for r in ROWS[start:]]},
                          f, indent=2)
            print(f"# wrote {out}", flush=True)


if __name__ == "__main__":
    main()
