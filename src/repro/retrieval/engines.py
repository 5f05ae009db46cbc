"""Retrieval-engine registry (DESIGN.md §8).

The experiment grid compares sampling methods across *retrieval systems*, so
each vector index is a first-class registered object behind one protocol —
the same pluggable-component pattern as the label-prop registry in
``core/engines.py`` — rather than a string branch inside the runner.  The
registry lives here, below both of its consumers (``retrieval/experiment.py``
and the ``repro.eval`` grid subsystem, which re-exports it), so neither
package depends upward on the other; chunked multi-query search, backend
selection and global-id mapping live one layer up in
``retrieval/search_core.SearchSession``.

An engine implements the :class:`RetrievalEngine` protocol:

  * ``build(key, vecs)`` — one-time index construction over the corpus
    vectors (f32[N, D]); returns an engine-private index pytree.
  * ``search(index, queries, k)`` — ANN/exact top-k; returns i32[Q, k] ids
    into the ``vecs`` the index was built from (−1 padding for misses).
  * ``search_scored(index, queries, k)`` — the scored variant ``search``
    slices: (scores f32[Q, k], ids i32[Q, k]).  Scores are inner products
    (for lsh: only when ``rerank > 0`` — the no-rerank path returns
    positive Hamming distances), which is what lets the serving tier's
    live append buffers merge engine results with a fresh exact scan
    (serve/ingest.py) by comparing scores across the two sources.

Registered engines:

  * ``exact``   — blocked brute-force inner product (the oracle).
  * ``ivfflat`` — k-means inverted lists, the paper's pgvector index;
                  ``n_lists`` auto-shrinks for small sampled corpora.
  * ``lsh``     — sign-random-projection Hamming search with exact rerank
                  (the paper cites LSH [3] as an index option).
  * ``tfidf``   — IDF-reweighted exact search: dimensions active in few
                  corpus vectors are up-weighted by log1p(N/df).  Over the
                  bag-of-words ``tfidf_vectors`` embedder this is classic
                  tf-idf ranking; over dense encoder vectors df ≈ N, the
                  weights flatten, and it degrades gracefully to ``exact``.

Engines are frozen dataclasses so callers can tune hyper-parameters with
``dataclasses.replace`` without mutating the registry's shared instance.
Every engine carries a ``backend`` field naming a scoring backend from
``retrieval/backends.py`` (``jnp`` reference or ``pallas`` kernels); the
search core sets it uniformly, so the kernel path is a config string, not a
per-index fork.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Protocol, runtime_checkable

import jax.numpy as jnp

from repro.retrieval.backends import get_backend
from repro.retrieval.exact import exact_topk
from repro.retrieval.ivfflat import build_ivfflat, search_ivfflat
from repro.retrieval.lsh import build_lsh, search_lsh


@runtime_checkable
class RetrievalEngine(Protocol):
    """A vector index behind a uniform build/search interface."""

    name: str

    def build(self, key, vecs: jnp.ndarray) -> Any:
        """Corpus vectors f32[N, D] -> engine-private index."""
        ...

    def search(self, index: Any, queries: jnp.ndarray, *,
               k: int) -> jnp.ndarray:
        """Queries f32[Q, D] -> top-k ids i32[Q, k] into the built corpus."""
        ...

    def search_scored(self, index: Any, queries: jnp.ndarray, *,
                      k: int) -> Any:
        """Queries f32[Q, D] -> (scores f32[Q, k], ids i32[Q, k])."""
        ...


_REGISTRY: Dict[str, RetrievalEngine] = {}


def register_retrieval_engine(cls):
    """Class decorator: instantiate and register an engine under its name."""
    engine = cls()
    _REGISTRY[engine.name] = engine
    return cls


def get_retrieval_engine(name: str) -> RetrievalEngine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown retrieval engine {name!r}; registered engines: "
            f"{', '.join(available_retrieval_engines())}") from None


def available_retrieval_engines() -> tuple:
    return tuple(sorted(_REGISTRY))


@register_retrieval_engine
@dataclasses.dataclass(frozen=True)
class ExactEngine:
    """Blocked brute-force top-k — the recall oracle for the ANN engines."""

    block: int = 2048
    backend: str = "jnp"
    name: str = "exact"

    def build(self, key, vecs):
        del key  # deterministic
        # build-time backend hook: int8 quantizes the corpus once here
        return get_backend(self.backend).prepare_corpus(vecs)

    def search(self, index, queries, *, k: int):
        return self.search_scored(index, queries, k=k)[1]

    def search_scored(self, index, queries, *, k: int):
        return exact_topk(queries, index, k=k, block=self.block,
                          backend=self.backend)


@register_retrieval_engine
@dataclasses.dataclass(frozen=True)
class IVFFlatEngine:
    """k-means inverted lists (pgvector ``ivfflat``).  ``n_lists`` shrinks to
    N//8 on small sampled corpora so every list keeps enough members."""

    n_lists: int = 64
    nprobe: int = 8
    backend: str = "jnp"
    name: str = "ivfflat"

    def build(self, key, vecs):
        n_lists = min(self.n_lists, max(1, vecs.shape[0] // 8))
        return build_ivfflat(key, vecs, n_lists=n_lists)

    def search(self, index, queries, *, k: int):
        return self.search_scored(index, queries, k=k)[1]

    def search_scored(self, index, queries, *, k: int):
        nprobe = min(self.nprobe, index.centroids.shape[0])
        return search_ivfflat(index, queries, k=k, nprobe=nprobe,
                              backend=self.backend)


@register_retrieval_engine
@dataclasses.dataclass(frozen=True)
class LSHEngine:
    """Sign-random-projection Hamming search with exact rerank of the top
    ``rerank`` Hamming candidates (clamped to [k, N])."""

    n_bits: int = 128
    rerank: int = 64
    backend: str = "jnp"
    name: str = "lsh"

    def build(self, key, vecs):
        return build_lsh(key, vecs, n_bits=self.n_bits)

    def search(self, index, queries, *, k: int):
        return self.search_scored(index, queries, k=k)[1]

    def search_scored(self, index, queries, *, k: int):
        n = index.vecs.shape[0]
        rerank = min(max(self.rerank, k), n) if self.rerank > 0 else 0
        return search_lsh(index, queries, k=k, rerank=rerank,
                          backend=self.backend)


class TfIdfIndex(NamedTuple):
    vecs: Any              # (N, D) IDF-weighted corpus, backend-prepared
                           # (QuantizedCorpus under the int8 backend)
    weights: jnp.ndarray   # (D,) per-dimension log1p(N/df)


@register_retrieval_engine
@dataclasses.dataclass(frozen=True)
class TfIdfEngine:
    """IDF-reweighted exact search: df_j = |{i : vecs[i, j] > 0}|, corpus
    dimension j scaled by log1p(N/df_j).  The weight is applied on the
    corpus side only, so scores are sum_j w_j q_j d_j (one IDF factor)."""

    block: int = 2048
    backend: str = "jnp"
    name: str = "tfidf"

    def build(self, key, vecs):
        del key  # deterministic
        n = vecs.shape[0]
        df = jnp.sum(vecs > 0, axis=0).astype(jnp.float32) + 1.0
        w = jnp.log1p(n / df)
        # IDF folds in before the backend hook so int8 quantizes the
        # weighted rows the scan will actually score
        return TfIdfIndex(get_backend(self.backend).prepare_corpus(
            vecs * w[None, :]), w)

    def search(self, index, queries, *, k: int):
        return self.search_scored(index, queries, k=k)[1]

    def search_scored(self, index, queries, *, k: int):
        return exact_topk(queries, index.vecs, k=k, block=self.block,
                          backend=self.backend)
