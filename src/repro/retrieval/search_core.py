"""Search core front door — Layer 3 (DESIGN.md §9).

One :class:`SearchSession` is the single implementation of "build an index
once, answer many queries" that every consumer routes through: the
experiment grid (``eval/runner.py``), the Table I/II experiment
(``retrieval/experiment.py``), the evaluation CLI (``launch/evaluate.py``)
and the online serving path (``serve/engine.py`` — the paper's Fig. 5
query → embed → ANN component).  Offline eval and online serving therefore
share one code path, so a backend or sharding change benchmarked in the
grid is exactly what serves traffic.

Configuration is one declarative :class:`SearchConfig`:

  * ``engine``  — a registered retrieval engine (retrieval/engines.py);
  * ``backend`` — a registered scoring backend (retrieval/backends.py,
    Layer 1): ``jnp`` reference, ``pallas`` kernels, or ``int8``
    quantized scan + float rerank (applied before ``engine.build`` so
    build-time hooks like int8 corpus quantization see it);
  * ``sharded``/``mesh`` — route searches through the mesh-partitioned
    Layer 2 (retrieval/sharded.py);
  * ``query_chunk`` — chunked multi-query batching, so the probe gather
    stays O(chunk · cand · d) regardless of the query load;
  * ``engine_opts`` — hyper-parameter overrides applied with
    ``dataclasses.replace`` (e.g. ``{"n_lists": 16}``).

Unknown engine/backend names fail fast with the registry's error message
(the ``core/engines.py`` UX).  ``k`` is clamped to the indexed corpus size
and padded back with −1 ids, so tiny sampled corpora never crash a search.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharded_corpus import ShardedCorpus
from repro.obs import trace
from repro.obs import memory as obs_memory
from repro.retrieval.backends import get_backend
from repro.retrieval.engines import get_retrieval_engine
from repro.retrieval.sharded import sharded_build, sharded_search


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Declarative search-core configuration (engine × backend × shard).

    ``streamed=True`` shards the corpus from birth: the host array is
    streamed chunk-wise into per-device buffers
    (distributed/sharded_corpus.ShardedCorpus) and the index is built
    per shard (retrieval/sharded.sharded_build) — no device ever holds
    the global corpus or the global index.  Passing a ``ShardedCorpus``
    directly as ``corpus_vecs`` has the same effect; both imply
    ``sharded=True``.
    """

    engine: str = "exact"
    backend: str = "jnp"
    sharded: bool = False
    mesh: Any = None              # jax.sharding.Mesh when sharded
    streamed: bool = False        # shard-local build from birth
    stream_chunk: int = 65536     # host->device streaming chunk rows
    query_chunk: int = 256
    engine_opts: Optional[Mapping[str, Any]] = None


class SearchSession:
    """Build-once, chunked multi-query search over one corpus.

    ``corpus_vecs`` f32[N, D] are indexed once at construction (globally —
    sharding distributes scoring, never index statistics); ``search`` then
    answers any number of query batches.  When ``ids_map`` is given (the
    sample's kept entity ids), results map from index-local rows back to
    global ids, with −1 for misses — the contract the eval grid's metric
    stages consume.
    """

    def __init__(self, corpus_vecs, config: Optional[SearchConfig] = None,
                 *, key: Optional[jax.Array] = None,
                 ids_map: Optional[np.ndarray] = None, **overrides):
        cfg = config or SearchConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        engine = get_retrieval_engine(cfg.engine)   # registry error UX
        get_backend(cfg.backend)                    # fail fast, same UX
        born = corpus_vecs if isinstance(corpus_vecs, ShardedCorpus) else None
        if born is None and cfg.streamed:
            if cfg.mesh is None:
                raise ValueError("streamed build needs a mesh; pass "
                                 "SearchConfig(mesh=...) (launch.mesh "
                                 "helpers)")
            born = ShardedCorpus.from_host(corpus_vecs, mesh=cfg.mesh,
                                           chunk_rows=cfg.stream_chunk)
        if born is not None:
            # a sharded-from-birth corpus forces the sharded query plans
            cfg = dataclasses.replace(cfg, sharded=True, streamed=True,
                                      mesh=born.mesh)
        if cfg.sharded and cfg.mesh is None:
            raise ValueError("sharded search needs a mesh; pass "
                             "SearchConfig(mesh=...) (launch.mesh helpers)")
        if cfg.sharded and cfg.backend == "int8" and born is None:
            # lifted on the born path (per-shard scales + float rerank);
            # the global-partition path keeps the rejection (DESIGN.md §13)
            raise ValueError(
                "sharded search does not support the 'int8' backend (the "
                "row-shard padding sentinel would destroy the quantization "
                "scale); use backend='jnp' or 'pallas'")
        if cfg.engine_opts:
            engine = dataclasses.replace(engine, **dict(cfg.engine_opts))
        self.config = cfg
        self.engine = dataclasses.replace(engine, backend=cfg.backend)
        self._born = born
        if born is not None:
            self.corpus_size = born.n
        else:
            vecs = jnp.asarray(corpus_vecs)
            self.corpus_size = int(vecs.shape[0])
        self.ids_map = None if ids_map is None else np.asarray(ids_map)
        if self.ids_map is not None and self.ids_map.size != self.corpus_size:
            raise ValueError(
                f"ids_map has {self.ids_map.size} entries for a corpus of "
                f"{self.corpus_size} vectors")
        with trace.jax_span(
                "search.build",
                compile_key=f"search.build/{cfg.engine}/{cfg.backend}",
                engine=cfg.engine, backend=cfg.backend,
                n=self.corpus_size, streamed=born is not None,
                shards=born.num_shards if born is not None else 1) as sp:
            bkey = key if key is not None else jax.random.PRNGKey(0)
            if born is not None:
                self.index = sharded_build(self.engine, born, bkey)
            else:
                self.index = self.engine.build(bkey, vecs)
            sp.declare(self.index)
        obs_memory.record_build_peak()

    def _search_chunk(self, queries: jnp.ndarray, k: int):
        cfg = self.config
        with trace.jax_span(
                "search.chunk",
                compile_key=(f"search.chunk/{cfg.engine}/{cfg.backend}/"
                             f"{self.corpus_size}/{queries.shape[0]}/{k}"),
                engine=cfg.engine, backend=cfg.backend,
                n=self.corpus_size, q=int(queries.shape[0]), k=k,
                sharded=cfg.sharded) as sp:
            if cfg.sharded:
                scores, ids = sharded_search(self.engine, self.index,
                                             queries, k=k, mesh=cfg.mesh)
            else:
                scores, ids = self.engine.search_scored(self.index, queries,
                                                        k=k)
            sp.declare(ids)
        with trace.span("search.readback", q=int(queries.shape[0])):
            return np.asarray(scores), np.asarray(ids)

    def search_scored(self, queries, *, k: int):
        """(scores f32[Q, k], ids i32[Q, k]) for a query batch — −inf/−1
        padding for misses, chunked by ``query_chunk``, ids mapped through
        ``ids_map`` when set.  Scores are the engine's final ranking scores
        (inner products for every engine except no-rerank lsh, which ranks
        by positive Hamming distance), which is what the serving tier's
        live-ingest merge (serve/ingest.py) compares against its append
        buffer's exact scan."""
        q = np.asarray(queries)
        k_eff = max(1, min(k, self.corpus_size))
        chunk = self.config.query_chunk
        with trace.span("search.scored", q=int(q.shape[0]),
                        chunks=-(-q.shape[0] // chunk)):
            parts = []
            for i in range(0, q.shape[0], chunk):
                with trace.jax_span("search.upload") as up:
                    part = jnp.asarray(q[i:i + chunk])
                    up.declare(part)
                parts.append(self._search_chunk(part, k_eff))
            if parts:
                scores = np.concatenate([p[0] for p in parts], 0)
                local = np.concatenate([p[1] for p in parts], 0)
            else:
                scores = np.full((0, k_eff), -np.inf, np.float32)
                local = np.zeros((0, k_eff), np.int32)
            if k_eff < k:
                scores = np.pad(scores, ((0, 0), (0, k - k_eff)),
                                constant_values=-np.inf)
                local = np.pad(local, ((0, 0), (0, k - k_eff)),
                               constant_values=-1)
            if self.ids_map is not None:
                local = np.where(local >= 0,
                                 self.ids_map[np.clip(local, 0, None)], -1)
        return scores, local

    def search(self, queries, *, k: int) -> np.ndarray:
        """Top-k ids i32[Q, k] for a query batch (−1 padding for misses);
        chunked by ``query_chunk``, mapped through ``ids_map`` when set."""
        return self.search_scored(queries, k=k)[1]
