"""Incremental corpus ingestion for the serving tier (DESIGN.md §14).

A :class:`LiveIndex` is a :class:`~repro.retrieval.search_core.
SearchSession` that accepts new documents while it serves: ``append(docs)``
lands rows in a fixed-capacity append buffer that every search scans
alongside the frozen index, and a compaction threshold triggers a
background rebuild through the normal session build path (``sharded_build``
on the streamed path) — serving never stops for a reindex.

Dataflow per search::

    queries ──> frozen SearchSession.search_scored ──┐
            └─> append-buffer exact scan ────────────┴─> score merge, top-k

The two sides merge by score, which works because every engine's
``search_scored`` returns its FINAL ranking scores as inner products
(lsh must therefore run with ``rerank > 0`` — enforced at construction;
the no-rerank Hamming scale is not comparable to a dot product).  The
buffer is scanned in f32 regardless of the session backend: buffers are
small, and quantization is a bandwidth optimisation for the big frozen
index, not its tail.

tf-idf is the one engine whose index statistics go stale under appends:
the frozen rows have ``w = log1p(n/df)`` folded in at build time.  Rather
than rebuilding per append, the O(D) document-frequency vector is
maintained incrementally and the refreshed weights fold into the QUERY:
``q ⊙ (w_live / w_frozen)`` scores the frozen rows exactly as a rebuild
would (``(q ⊙ w'/w) · (v ⊙ w) = q · (v ⊙ w')``), and ``q ⊙ w_live``
scores the raw buffer rows — so append-then-search stays set-equal to a
from-scratch rebuild without touching the index.

Buffer mechanics: capacity is fixed per compiled shape and grows
geometrically (so steady-state appends and searches never retrace — the
live-row count is a dynamic scalar), rows land via a jitted
``dynamic_update_slice`` (NOT donated: an in-flight search may still hold
the previous buffer), and on the sharded path the buffer is one more
shard-local structure built with the ``distributed/sharded_corpus.py``
streaming geometry and merged through the same all-gather + top-k path as
every sharded engine plan (``retrieval/sharded.sharded_buffer_topk``).

Compaction: when pending rows reach ``compact_threshold``, the pending
prefix is folded into a NEW session built on a worker thread from the host
mirror while searches keep hitting the old (session, buffer) snapshot;
the swap happens under the lock, rows appended mid-build stay pending, and
ids are stable across compactions (append order is the global id order).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed.sharded_corpus import sharded_row_buffer
from repro.obs import REGISTRY, trace
from repro.obs.locks import make_rlock
from repro.obs.metrics import Registry
from repro.retrieval.search_core import SearchConfig, SearchSession
from repro.retrieval.sharded import sharded_buffer_topk

__all__ = ["IngestConfig", "LiveIndex"]


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Live-ingest knobs.

    ``append_cap`` is the initial device-buffer capacity in rows (grows by
    doubling — each growth is one new compiled shape, so leave headroom);
    ``compact_threshold`` is the pending-row count that triggers a rebuild;
    ``background=False`` compacts inline (deterministic; tests and
    single-threaded drivers)."""

    append_cap: int = 256
    compact_threshold: int = 4096
    background: bool = True


@functools.partial(jax.jit, static_argnames=("k", "id_base"))
def _buffer_topk(queries, buf, n_valid, *, k: int, id_base: int):
    """Exact top-k over the (single-device) append buffer: rows at position
    ≥ ``n_valid`` (a dynamic scalar — appends never retrace) mask to −inf
    and can never displace a live row; ids offset by the frozen size."""
    s = jnp.dot(queries, buf.T, precision=lax.Precision.HIGHEST
                ).astype(jnp.float32)
    pos = jnp.arange(buf.shape[0], dtype=jnp.int32)
    s = jnp.where((pos < n_valid)[None, :], s, -jnp.inf)
    top_s, top_p = lax.top_k(s, k)
    top_i = jnp.where(jnp.isfinite(top_s), id_base + top_p, -1)
    return top_s, top_i


@functools.partial(jax.jit, donate_argnums=())
def _buffer_write(buf, rows, start):
    # deliberately NOT donated: a concurrent search may still hold the
    # previous buffer array (the lock covers the swap, not the compute)
    return lax.dynamic_update_slice(buf, rows, (start, jnp.int32(0)))


def _df_counts(rows: np.ndarray) -> np.ndarray:
    return (np.asarray(rows) > 0).sum(axis=0).astype(np.int64)


class LiveIndex:
    """Build-once-append-forever search target: a frozen
    :class:`SearchSession` plus a live append buffer, one ``search``/
    ``search_scored`` contract (scores f32[Q, k], ids i32[Q, k], −inf/−1
    padding), ids stable across compactions.

    Metrics (DESIGN.md §12, the shared registry): ``serve.ingest.appended``
    rows counter, ``serve.ingest.pending`` gauge, ``serve.ingest.
    compactions`` counter, ``serve.ingest.searches`` counter; compactions
    run under a ``serve.compact`` span.
    """

    def __init__(self, corpus_vecs, config: Optional[SearchConfig] = None,
                 *, key: Optional[jax.Array] = None,
                 ingest: Optional[IngestConfig] = None,
                 registry: Registry = REGISTRY, **overrides):
        self._host = np.ascontiguousarray(
            np.asarray(corpus_vecs, np.float32))
        if self._host.ndim != 2:
            raise ValueError(
                f"live corpus must be 2-D (N, D); got {self._host.shape}")
        self._lock = make_rlock("live-index")
        self.ingest = ingest or IngestConfig()
        if self.ingest.append_cap < 1 or self.ingest.compact_threshold < 1:
            raise ValueError("append_cap and compact_threshold must be >= 1")
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._registry = registry
        self._session = SearchSession(self._host, config, key=self._key,
                                      **overrides)
        cfg = self._session.config
        if cfg.engine == "lsh" and self._session.engine.rerank <= 0:
            raise ValueError(
                "live ingest needs score-comparable results to merge the "
                "append buffer; the lsh engine must run with rerank > 0 "
                "(no-rerank lsh ranks by Hamming distance, which cannot "
                "merge with the buffer's inner products)")
        self._tfidf = cfg.engine == "tfidf"
        self._frozen_df = (_df_counts(self._host) if self._tfidf else None)
        self._pending = np.zeros((0, self.dim), np.float32)
        self._cap = 0
        self._buf = None
        self._compactor: Optional[threading.Thread] = None
        self._compacting = False
        self._compact_error: Optional[BaseException] = None

    # -- geometry ----------------------------------------------------------
    # Every property below reads state the compactor swaps under the lock;
    # the RLock is re-entrant, so holders of the lock can use them freely.

    @property
    def dim(self) -> int:
        with self._lock:
            return int(self._host.shape[1])

    @property
    def frozen_n(self) -> int:
        """Rows covered by the frozen index (grows at each compaction)."""
        with self._lock:
            return self._session.corpus_size

    @property
    def pending_rows(self) -> int:
        with self._lock:
            return int(self._pending.shape[0])

    @property
    def n(self) -> int:
        """Total searchable rows (frozen + pending)."""
        with self._lock:
            return self.frozen_n + self.pending_rows

    @property
    def config(self) -> SearchConfig:
        with self._lock:
            return self._session.config

    @property
    def session(self) -> SearchSession:
        """The frozen index's session (replaced at each compaction)."""
        with self._lock:
            return self._session

    # -- ingest ------------------------------------------------------------

    def _rebuild_buffer(self) -> None:
        """Re-materialise the device buffer from the pending host rows
        (capacity growth, post-compaction shrink, or any sharded append —
        the sharded buffer re-streams; it is small by construction).
        Takes the (re-entrant) lock itself: callers already hold it, but
        the buffer swap must never run bare."""
        with self._lock:
            cfg = self._session.config
            need = max(self.pending_rows, 1)
            cap = max(self._cap, self.ingest.append_cap)
            while cap < need:
                cap *= 2
            self._cap = cap
            if cfg.sharded:
                self._buf = sharded_row_buffer(
                    self._pending, capacity=cap, dim=self.dim,
                    mesh=cfg.mesh, chunk_rows=cfg.stream_chunk)
            else:
                padded = np.zeros((cap, self.dim), np.float32)
                padded[:self.pending_rows] = self._pending
                self._buf = jnp.asarray(padded)

    def append(self, docs) -> Tuple[int, int]:
        """Land new document vectors f32[m, D]; returns their global id
        range [start, stop) — stable across compactions (append order is
        the id order).  May trigger a (background) compaction."""
        rows = np.asarray(docs, np.float32).reshape(-1, self.dim)
        if rows.shape[0] == 0:
            return self.n, self.n
        self._raise_pending_error()
        with self._lock, trace.span("serve.ingest.append",
                                    rows=int(rows.shape[0])):
            start = self.frozen_n + self.pending_rows
            old = self.pending_rows
            self._pending = np.concatenate([self._pending, rows], axis=0)
            cfg = self._session.config
            if cfg.sharded or self._buf is None \
                    or self.pending_rows > self._cap:
                self._rebuild_buffer()
            else:
                self._buf = _buffer_write(self._buf, jnp.asarray(rows),
                                          jnp.int32(old))
            self._registry.counter("serve.ingest.appended").inc(
                int(rows.shape[0]))
            self._registry.gauge("serve.ingest.pending").set(
                self.pending_rows)
            stop = start + int(rows.shape[0])
            if self.pending_rows >= self.ingest.compact_threshold:
                self.compact(background=self.ingest.background)
        return start, stop

    # -- search ------------------------------------------------------------

    def _weights(self, frozen_n: int, frozen_df, pending: np.ndarray):
        """(w_frozen, w_live) for the tf-idf query-side refresh: the df
        vector is O(D) and maintained exactly (integer counts), so the live
        weights equal what a from-scratch rebuild over frozen+pending rows
        would fold into the corpus."""
        total = frozen_n + pending.shape[0]
        df_frozen = frozen_df.astype(np.float32) + 1.0
        df_live = (frozen_df + _df_counts(pending)).astype(np.float32) + 1.0
        w_frozen = np.log1p(np.float32(frozen_n) / df_frozen)
        w_live = np.log1p(np.float32(total) / df_live)
        return w_frozen, w_live

    def search_scored(self, queries, *, k: int):
        """(scores f32[Q, k], ids i32[Q, k]) over frozen + pending rows —
        one consistent snapshot: every row appended before this call is
        visible, during a compaction included (the swap is atomic under
        the lock, so there is never a stale-index window)."""
        self._raise_pending_error()
        with self._lock:
            session = self._session
            buf, n_pend, cap = self._buf, self.pending_rows, self._cap
            frozen_n = session.corpus_size
            frozen_df = self._frozen_df
            pending = self._pending
        self._registry.counter("serve.ingest.searches").inc()
        q = np.asarray(queries, np.float32)
        total = frozen_n + n_pend
        k_eff = max(1, min(k, total))
        q_frozen = q
        if self._tfidf and n_pend:
            w_frozen, w_live = self._weights(frozen_n, frozen_df, pending)
            q_frozen = q * (w_live / np.maximum(w_frozen, 1e-30))[None, :]
            q_buf = q * w_live[None, :]
        else:
            q_buf = q
        fs, fi = session.search_scored(q_frozen, k=k_eff)
        if n_pend == 0:
            if k_eff < k:
                fs = np.pad(fs, ((0, 0), (0, k - k_eff)),
                            constant_values=-np.inf)
                fi = np.pad(fi, ((0, 0), (0, k - k_eff)),
                            constant_values=-1)
            return fs, fi
        cfg = session.config
        k_buf = min(k_eff, cap)   # cap from the snapshot: matches buf's shape
        if cfg.sharded:
            bs, bi = sharded_buffer_topk(buf, n_pend, jnp.asarray(q_buf),
                                         k=k_buf, mesh=cfg.mesh,
                                         id_base=frozen_n)
        else:
            bs, bi = _buffer_topk(jnp.asarray(q_buf), buf,
                                  jnp.int32(n_pend), k=k_buf,
                                  id_base=frozen_n)
        scores = np.concatenate([fs, np.asarray(bs)], axis=1)
        ids = np.concatenate([fi, np.asarray(bi)], axis=1)
        # stable descending merge: ties break toward the frozen side (the
        # backend tie policy's lower-id-first, since pending ids are ≥
        # frozen ids)
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k_eff]
        scores = np.take_along_axis(scores, order, axis=1)
        ids = np.take_along_axis(ids, order, axis=1)
        ids = np.where(np.isfinite(scores), ids, -1)
        if k_eff < k:
            scores = np.pad(scores, ((0, 0), (0, k - k_eff)),
                            constant_values=-np.inf)
            ids = np.pad(ids, ((0, 0), (0, k - k_eff)),
                         constant_values=-1)
        return scores, ids

    def search(self, queries, *, k: int) -> np.ndarray:
        """Top-k ids i32[Q, k] (−1 padding), frozen + pending rows."""
        return self.search_scored(queries, k=k)[1]

    # -- compaction --------------------------------------------------------

    def _raise_pending_error(self) -> None:
        with self._lock:
            err, self._compact_error = self._compact_error, None
        if err is not None:
            raise RuntimeError("background compaction failed") from err

    def compact(self, *, background: Optional[bool] = None,
                wait: bool = False) -> bool:
        """Fold the current pending rows into a fresh frozen index.

        The rebuild runs on a worker thread (``background=True``) while
        searches keep hitting the old snapshot; rows appended mid-build
        stay pending and remain searchable throughout.  Returns False when
        a compaction is already in flight (or nothing is pending)."""
        background = (self.ingest.background if background is None
                      else background)
        with self._lock:
            # the in-flight flag (not Thread.is_alive(), which is False
            # until start() and leaves a window where two compactions both
            # pass the check) — set here, cleared in the worker's finally
            if self._compacting:
                in_flight = True
            else:
                in_flight = False
                m = self.pending_rows
                if m == 0:
                    return False
                host_new = np.concatenate([self._host, self._pending[:m]],
                                          axis=0)
                cfg = self._session.config
                self._compacting = True
        if in_flight:
            if wait:
                self._join_compactor()
            return False

        def build():
            with trace.span("serve.compact", rows=int(host_new.shape[0]),
                            folded=m):
                session = SearchSession(host_new, cfg, key=self._key)
                df_new = _df_counts(host_new) if self._tfidf else None
                with self._lock:
                    self._host = host_new
                    self._session = session
                    self._frozen_df = df_new
                    self._pending = self._pending[m:]
                    self._rebuild_buffer()
                    self._registry.gauge("serve.ingest.pending").set(
                        self.pending_rows)
                self._registry.counter("serve.ingest.compactions").inc()

        if not background:
            try:
                build()
            finally:
                with self._lock:
                    self._compacting = False
            return True

        def guarded():
            try:
                build()
            except BaseException as e:   # surfaced on the next call
                with self._lock:
                    self._compact_error = e
            finally:
                with self._lock:
                    self._compacting = False

        t = threading.Thread(target=guarded, name="live-index-compact",
                             daemon=True)
        with self._lock:
            self._compactor = t
        t.start()
        if wait:
            self._join_compactor()
        return True

    def _join_compactor(self) -> None:
        # snapshot under the lock, join OUTSIDE it: the build thread needs
        # the lock to land its swap, so joining while holding it deadlocks
        with self._lock:
            t = self._compactor
        if t is not None:
            t.join()
        self._raise_pending_error()

    def flush(self) -> None:
        """Block until any in-flight compaction lands (tests, shutdown)."""
        self._join_compactor()
