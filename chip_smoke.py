#!/usr/bin/env python3
"""Smoke run of WindTunnel's main path on a TPU.

    python chip_smoke.py               # one chip: sampling, search, serving
    python chip_smoke.py --four-chips  # four chips: the sharded-from-birth
                                       # sampler and search, nothing else

Everything runs in this one process, through the front doors a user calls
(``SamplerSession``, ``SearchSession``, ``SearchServer``), at sizes users
run, on data made from ``--seed``:

* sampling — MS MARCO-shaped judgments (500,000 queries x 32 qrels, about
  8.0M judged entities), the ``configs/msmarco_windtunnel.py`` pipeline,
  label propagation on the ``pallas`` engine; its labels must equal the
  ``ell`` engine's on the same graph;
* search — 1,048,576 x 768 float32 vectors and 256 queries at k=10:
  exact on the pallas and int8 backends, ivfflat and lsh on pallas, each
  against the same engine on the jnp backend;
* serving — a one-tenant ``SearchServer`` over the same corpus answering
  64 requests, each equal to ``SearchSession.search`` on the same query.

Each phase prints one line: its cold time (compiles included), the
device's peak bytes so far and its comparison.  They are smoke timings,
not benchmark metrics.  The Pallas kernels must show up as TPU custom calls
in the compiled programs of what ran.  Any failure or exception ends the
run with a non-zero exit; a passing run ends with one JSON line naming the
device.  Without a TPU it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax

SEED = 0
DIM = 768
N_ROWS = 1_048_576           # one dense MS MARCO-width shard per chip
N_QUERIES = 256
K = 10
SAMPLE_QUERIES = 500_000     # judged queries; x32 qrels = 16.0M rows
SERVE_REQUESTS = 64
# pgvector's rule of thumb, lists = rows / 1000; 16 queries per probe
# chunk keeps the gathered candidates (16 x 8 lists of about 1,400 rows
# x 768 f32) < 1 GB
IVF_OPTS = {"n_lists": 1024, "nprobe": 8}
QUERY_CHUNK = {"exact": 256, "ivfflat": 16, "lsh": 64}
TIE_RTOL = 1e-3


class SmokeFailure(Exception):
    """A phase's output disagreed with its reference."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                 "this smoke never falls back to another device")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found "
                 f"{len(devices)}")
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def run_phase(name: str, device, fn) -> None:
    t0 = time.perf_counter()
    check = fn()
    cold_s = time.perf_counter() - t0
    gc.collect()
    print(f"smoke[{name}] cold_s={cold_s:.3f} "
          f"peak_bytes_in_use={peak_bytes(device)} {check} "
          "(smoke timing, not a benchmark metric)", flush=True)


def custom_calls(jitted, *args, **static) -> int:
    """TPU custom calls (Pallas kernels) in the compiled program."""
    return jitted.lower(*args, **static).compile().as_text().count(
        "tpu_custom_call")


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def make_qrels(num_queries: int, seed: int):
    """Host numpy qrels with the MS MARCO passage-judgment shape."""
    from repro.core import QRelTable
    from repro.data.synthetic import generate_qrels
    q, e, s, _, _, num_entities = generate_qrels(
        num_queries=num_queries, qrels_per_query=32, num_topics=96,
        seed=seed)
    import numpy as np
    return QRelTable(q, e, s, np.ones(q.shape[0], bool)), num_entities


def sampler_spec(**overrides):
    """The msmarco_windtunnel pipeline settings: tau quantile 0.5, fanout
    16, 5 LP rounds, a 0.15 draw — LP on the pallas engine by default."""
    from repro.configs.msmarco_windtunnel import CONFIG
    from repro.core import SamplerSpec
    fields = {"strategy": "windtunnel", "engine": "pallas",
              "target_size": CONFIG.sample_fraction, "seed": CONFIG.seed,
              **overrides}
    return SamplerSpec.from_config(CONFIG.windtunnel, **fields)


def one_device_session(qrels, num_queries, num_entities, engine):
    """Single-device SamplerSession over device-resident qrels."""
    import jax.numpy as jnp
    from repro.core import QRelTable, SamplerSession
    table = QRelTable(*(jnp.asarray(x) for x in qrels))
    return SamplerSession(table, num_queries=num_queries,
                          num_entities=num_entities,
                          spec=sampler_spec(engine=engine))


def sampling_phase(num_queries: int, seed: int) -> str:
    import numpy as np
    from repro.core import sampling_core
    qrels, num_entities = make_qrels(num_queries, seed)
    session = one_device_session(qrels, num_queries, num_entities, "pallas")
    labels = np.asarray(session.labels()[0])
    drawn = int(np.asarray(session.draw().entity_mask).sum())
    spec = session.spec
    edges = session.graph()[0]
    node_cap, edge_cap = session._lp_caps(edges)
    calls = custom_calls(sampling_core._labels_stage, edges,
                         engine="pallas", num_entities=num_entities,
                         max_degree=spec.max_degree, rounds=spec.lp_rounds,
                         node_cap=node_cap, edge_cap=edge_cap)
    del session
    require(calls > 0, "LP 'pallas' program holds no TPU custom call")
    ref = np.asarray(one_device_session(qrels, num_queries, num_entities,
                                        "ell").labels()[0])
    differ = int(np.sum(labels != ref))
    require(differ == 0, f"pallas LP labels differ from ell on {differ} "
                         f"of {labels.size} nodes")
    return (f"rows={qrels.query_ids.size} entities={num_entities} "
            f"communities={np.unique(labels).size} drawn={drawn} "
            f"labels_vs_ell=identical kernel_custom_calls={calls}")


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def topk_agreement(ids, ref_ids, ref_scores, k: int = K):
    """(recall@k against the reference, queries whose top-k id set differs
    where the reference's k-th and (k+1)-th scores are not a near tie)."""
    import numpy as np
    hits, bad = 0, 0
    for q in range(ids.shape[0]):
        got, want = set(ids[q, :k].tolist()), set(ref_ids[q, :k].tolist())
        hits += len(got & want)
        if got != want:
            sk, sk1 = float(ref_scores[q, k - 1]), float(ref_scores[q, k])
            if not abs(sk - sk1) <= TIE_RTOL * abs(sk):
                bad += 1
    return hits / (ids.shape[0] * k), bad


def search_config(engine: str, backend: str, **overrides):
    from repro.retrieval.search_core import SearchConfig
    return SearchConfig(
        engine=engine, backend=backend, query_chunk=QUERY_CHUNK[engine],
        engine_opts=IVF_OPTS if engine == "ivfflat" else None, **overrides)


def search_case(corpus, queries, engine: str, backend: str, ref) -> str:
    import numpy as np
    from repro.retrieval.search_core import SearchSession
    session = SearchSession(corpus, search_config(engine, backend))
    lists = ""
    if engine == "ivfflat":       # every corpus row must be in some list
        held = int(session.index.mask.sum())
        require(held == corpus.shape[0], f"ivfflat index holds {held} of "
                                         f"{corpus.shape[0]} rows")
        lists = f"rows_in_lists={held} list_cap={session.index.ids.shape[1]} "
    _, ids = session.search_scored(queries, k=K)
    chunk = queries[:QUERY_CHUNK[engine]]
    calls = custom_calls(
        jax.jit(lambda index, q: session.engine.search_scored(index, q,
                                                              k=K)),
        session.index, chunk)
    del session
    require(calls > 0, f"{engine}/{backend} search holds no TPU custom call")
    recall, bad = topk_agreement(np.asarray(ids), *ref)
    require(bad == 0, f"{engine}/{backend}: {bad} queries' top-{K} sets "
                      f"differ from {engine}/jnp away from a tie")
    return (f"{lists}recall@{K}_vs_{engine}/jnp={recall:.6f} "
            f"mismatched_queries=0 kernel_custom_calls={calls}")


def reference(corpus, queries, engine: str):
    """Same engine on the jnp backend, top-(k+1) for the tie test."""
    import numpy as np
    from repro.retrieval.search_core import SearchSession
    session = SearchSession(corpus, search_config(engine, "jnp"))
    scores, ids = session.search_scored(queries, k=K + 1)
    return np.asarray(ids), np.asarray(scores)


def make_corpus(seed: int, rows: int):
    import jax.numpy as jnp
    import numpy as np
    kc, kq = jax.random.split(jax.random.PRNGKey(seed))
    corpus = jax.random.normal(kc, (rows, DIM), jnp.float32)
    queries = np.asarray(jax.random.normal(kq, (N_QUERIES, DIM),
                                           jnp.float32))
    return corpus, queries


def serving_phase(corpus, queries) -> str:
    import numpy as np
    from repro.retrieval.search_core import SearchSession
    from repro.serve import SchedulerConfig, SearchServer
    cfg = search_config("exact", "pallas")
    sched = SchedulerConfig(max_batch=32, k_max=K)
    server = SearchServer(lambda tenant: corpus, config=cfg,
                          scheduler=sched, max_tenants=1)
    batch = queries[:SERVE_REQUESTS]
    pending = [server.submit(q, k=K, tenant="t0") for q in batch]
    require(all(p is not None for p in pending), "server refused a request")
    done = server.drain()
    require(done == SERVE_REQUESTS, f"drain completed {done} requests")
    got = np.stack([p.result()[1] for p in pending])
    live = server.tenants.get("t0").session
    calls = custom_calls(
        jax.jit(lambda index, q: live.engine.search_scored(index, q, k=K)),
        live.index, batch[:sched.max_batch])
    ticks = server.scheduler.ticks
    del server, live
    require(calls > 0, "served search holds no TPU custom call")
    want = SearchSession(corpus, cfg).search(batch, k=K)
    differ = int(np.sum(np.any(got != want, axis=1)))
    require(differ == 0, f"{differ} served results differ from "
                         "SearchSession.search")
    return (f"requests={done} ticks={ticks} "
            f"results_vs_SearchSession=identical kernel_custom_calls={calls}")


def one_chip(args, device) -> None:
    run_phase("sampling", device,
              lambda: sampling_phase(SAMPLE_QUERIES, args.seed))
    corpus, queries = make_corpus(args.seed, N_ROWS)
    exact_ref = reference(corpus, queries, "exact")
    for engine, backend in (("exact", "pallas"), ("exact", "int8"),
                            ("ivfflat", "pallas"), ("lsh", "pallas")):
        run_phase(f"search/{engine}/{backend}", device, lambda: search_case(
            corpus, queries, engine, backend,
            exact_ref if engine == "exact"
            else reference(corpus, queries, engine)))
    run_phase("serving", device, lambda: serving_phase(corpus, queries))


# ---------------------------------------------------------------------------
# four chips: the sharded-from-birth sampler and search
# ---------------------------------------------------------------------------

def balanced(what: str) -> str:
    """Resident bytes per device, none above 1.5x the mean."""
    from repro.obs.memory import resident_bytes_per_device
    gc.collect()
    per = list(resident_bytes_per_device().values())
    quarter = sum(per) / len(per)
    require(max(per) <= 1.5 * quarter,
            f"{what}: a device holds more than 1.5x its share: {per}")
    return f"resident_bytes={per}"


def streamed_sampling_phase(num_queries: int, seed: int, mesh) -> str:
    import numpy as np
    from repro.core import SamplerSession
    qrels, num_entities = make_qrels(num_queries, seed)
    ref = np.asarray(one_device_session(qrels, num_queries, num_entities,
                                        "pallas").labels()[0])
    session = SamplerSession(qrels, num_queries=num_queries,
                             num_entities=num_entities,
                             spec=sampler_spec(streamed=True, mesh=mesh))
    labels = session.labels()[0]
    layout = balanced("streamed sampler")
    differ = int(np.sum(np.asarray(labels) != ref))
    require(differ == 0, f"4-chip LP labels differ from 1-chip on {differ} "
                         f"of {ref.size} nodes")
    return (f"rows={qrels.query_ids.size} entities={num_entities} "
            f"labels_vs_1chip=identical {layout}")


def host_reference(host, queries, chunk_rows: int = 1 << 18):
    """Chunked float32 exact top-(k+1) on the host, independent of the
    device code: (ids, scores)."""
    import numpy as np
    best_s = np.full((queries.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((queries.shape[0], 0), np.int64)
    for start in range(0, host.shape[0], chunk_rows):
        s = queries @ host[start:start + chunk_rows].T
        cat_s = np.concatenate([best_s, s], axis=1)
        cat_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(start, start + s.shape[1]),
                                     s.shape)], axis=1)
        top = np.argpartition(-cat_s, K, axis=1)[:, :K + 1]
        top_s = np.take_along_axis(cat_s, top, axis=1)
        order = np.argsort(-top_s, axis=1, kind="stable")
        best_s = np.take_along_axis(top_s, order, axis=1)
        best_i = np.take_along_axis(np.take_along_axis(cat_i, top, axis=1),
                                    order, axis=1)
    return best_i, best_s


def streamed_search_phase(host, queries, ref, backend: str, mesh) -> str:
    import numpy as np
    from repro.retrieval.search_core import SearchSession
    session = SearchSession(host, search_config("exact", backend,
                                                streamed=True, mesh=mesh))
    layout = balanced(f"streamed {backend} corpus")
    _, ids = session.search_scored(queries, k=K)
    del session
    recall, bad = topk_agreement(np.asarray(ids), *ref)
    require(bad == 0, f"4-chip exact/{backend}: {bad} queries' top-{K} "
                      "sets differ from the host reference away from a tie")
    return (f"rows={host.shape[0]} recall@{K}_vs_host_f32={recall:.6f} "
            f"mismatched_queries=0 {layout}")


def four_chips(args, device) -> None:
    import numpy as np
    from repro.launch.mesh import parse_mesh
    mesh = parse_mesh("auto")
    run_phase("4chip/sampling", device, lambda: streamed_sampling_phase(
        SAMPLE_QUERIES, args.seed, mesh))
    host = np.empty((4 * N_ROWS, DIM), np.float32)
    for i, dev in enumerate(jax.devices()[:4]):     # made on the chips
        with jax.default_device(dev):
            part, queries = make_corpus(args.seed + i, N_ROWS)
            host[i * N_ROWS:(i + 1) * N_ROWS] = np.asarray(part)
        del part
    ref = host_reference(host, queries)
    for backend in ("pallas", "int8"):
        run_phase(f"4chip/search/exact/{backend}", device,
                  lambda: streamed_search_phase(host, queries, ref, backend,
                                                mesh))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded-from-birth path on 4 chips")
    p.add_argument("--seed", type=int, default=SEED)
    args = p.parse_args(argv)
    count = 4 if args.four_chips else 1
    devices = require_tpu(count)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        (four_chips if args.four_chips else one_chip)(args, devices[0])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
