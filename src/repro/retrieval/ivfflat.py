"""IVF-Flat vector index — the pgvector ``ivfflat`` index of the paper's
experiments, in JAX.

Build: k-means (Lloyd) clusters the corpus into ``n_lists`` inverted lists,
stored as a padded ELL block (n_lists, cap, d) so probing is dense gathers;
``cap`` is the longest list's length, so no row is ever left out.
Search: score the query against centroids, probe the ``nprobe`` nearest
lists, then score their members through the scoring-backend registry's
``gathered_topk`` primitive (retrieval/backends.py) — pure jnp or the
Pallas per-query candidate kernel. Search is static-shape and jit-able.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.retrieval.backends import get_backend

_F32 = lax.Precision.HIGHEST      # XLA's TPU default rounds f32 to bf16


class IVFFlatIndex(NamedTuple):
    centroids: jnp.ndarray   # (n_lists, d)
    vecs: jnp.ndarray        # (n_lists, cap, d)
    ids: jnp.ndarray         # (n_lists, cap) original ids, -1 padding
    mask: jnp.ndarray        # (n_lists, cap)


def assign_lists(data: jnp.ndarray, cent: jnp.ndarray) -> jnp.ndarray:
    """Nearest centroid (squared L2) of each row: (N,) list ids."""
    d2 = (jnp.sum(data ** 2, 1)[:, None]
          - 2.0 * jnp.dot(data, cent.T, precision=_F32)
          + jnp.sum(cent ** 2, 1)[None])
    return jnp.argmin(d2, axis=1)


def kmeans(key, data: jnp.ndarray, n_clusters: int, iters: int = 10):
    """Lloyd's algorithm; returns centroids (n_clusters, d)."""
    n = data.shape[0]
    init_idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = data[init_idx]

    def step(cent, _):
        assign = assign_lists(data, cent)
        sums = jax.ops.segment_sum(data, assign, num_segments=n_clusters)
        cnts = jax.ops.segment_sum(jnp.ones((n, 1), data.dtype), assign,
                                   num_segments=n_clusters)
        new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0), cent)
        return new, None

    cent, _ = lax.scan(step, cent, None, length=iters)
    return cent


def list_ranks(assign: jnp.ndarray, n_lists: int):
    """Slot of each row within its list, and each list's length:
    (rank (N,), counts (n_lists,)).  Rows whose list id is out of range
    (a caller's pad segment) are ranked but not counted."""
    n = assign.shape[0]
    order = jnp.argsort(assign, stable=True)
    sa = assign[order]
    starts = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
    iota = jnp.arange(n, dtype=jnp.int32)
    ranked = iota - lax.cummax(jnp.where(starts, iota, 0))
    rank = jnp.zeros_like(ranked).at[order].set(ranked)
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), assign,
                                 num_segments=n_lists)
    return rank, counts


def fill_lists(vecs, ids, assign, rank, n_lists: int, cap: int):
    """Scatter rows straight to their (list, slot): the padded ELL block
    (vecs (n_lists, cap, d), ids (n_lists, cap), mask (n_lists, cap)).
    ``cap`` must hold the longest list; out-of-range list ids drop."""
    d = vecs.shape[1]
    lvecs = jnp.zeros((n_lists, cap, d), vecs.dtype).at[assign, rank].set(
        vecs, mode="drop")
    lids = jnp.full((n_lists, cap), -1, jnp.int32).at[assign, rank].set(
        ids, mode="drop")
    lmask = jnp.zeros((n_lists, cap), bool).at[assign, rank].set(
        True, mode="drop")
    return lvecs, lids, lmask


def build_ivfflat(key, corpus: jnp.ndarray, *, n_lists: int,
                  kmeans_iters: int = 10) -> IVFFlatIndex:
    """k-means lists sized to the longest list (read back to the host
    once), so every corpus row is in the index."""
    n = corpus.shape[0]
    cent = kmeans(key, corpus, n_lists, kmeans_iters)
    assign = assign_lists(corpus, cent)                   # (N,)
    rank, counts = list_ranks(assign, n_lists)
    cap = int(jnp.max(counts))
    vecs, ids, mask = fill_lists(corpus, jnp.arange(n, dtype=jnp.int32),
                                 assign, rank, n_lists, cap)
    return IVFFlatIndex(cent, vecs, ids, mask)


def probe_candidates(index: IVFFlatIndex, queries: jnp.ndarray, *,
                     nprobe: int):
    """Select the ``nprobe`` nearest lists per query and gather their
    members as a per-query candidate set: (cand_vecs (Q, nprobe·cap, d),
    cand_ids (Q, nprobe·cap) with −1 marking padding slots)."""
    cscore = jnp.dot(queries, index.centroids.T,
                     precision=_F32)                       # (Q, n_lists)
    _, probe = lax.top_k(cscore, nprobe)                   # (Q, nprobe)
    # gather (list, slot) rows: indexing the leading axis alone makes the
    # TPU compiler copy the whole (n_lists, cap, d) index first
    slot = (probe[..., None], jnp.arange(index.ids.shape[1])[None, None])
    vecs = index.vecs[slot]                           # (Q, nprobe, cap, d)
    ids = index.ids[slot]                                  # (Q, nprobe, cap)
    mask = index.mask[slot]
    qn, d = queries.shape
    cand_vecs = vecs.reshape(qn, -1, d)
    cand_ids = jnp.where(mask, ids, -1).reshape(qn, -1)
    return cand_vecs, cand_ids


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "backend"))
def search_ivfflat(index: IVFFlatIndex, queries: jnp.ndarray, *, k: int,
                   nprobe: int = 8, backend: str = "jnp"):
    """queries (Q, d) -> (scores (Q, k), ids (Q, k)); inner product metric,
    probe-scoring dispatched through ``backend``."""
    cand_vecs, cand_ids = probe_candidates(index, queries, nprobe=nprobe)
    return get_backend(backend).gathered_topk(queries, cand_vecs, cand_ids,
                                              k=k)
