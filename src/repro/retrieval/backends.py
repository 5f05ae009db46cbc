"""Scoring-backend registry — Layer 1 of the search core (DESIGN.md §9).

Every retrieval engine bottoms out in one of three scoring primitives:

  * ``topk``          — dense inner-product top-k against a shared corpus
                        (exact / tfidf search, lsh rerank oracle);
  * ``hamming_topk``  — packed sign-code Hamming top-k (the lsh scan);
  * ``gathered_topk`` — per-query candidate-set top-k (the ivfflat probe
                        scoring, where each query scores its own gathered
                        lists).

A backend is a registered implementation of all three behind one protocol —
the same pluggable-component pattern as ``core/engines.py`` — so the choice
of execution strategy (pure-XLA jnp vs the Pallas kernels) is a config
string on any engine rather than a fork in each index.  Registered:

  * ``jnp``    — pure-jnp reference: blocked streaming top-k for the dense
                 scan (the (Q, N) score matrix is never materialised),
                 the kernel oracles for Hamming and gathered scoring.
  * ``pallas`` — the fused Pallas kernels (kernels/topk_scoring,
                 kernels/lsh_hamming); interpret mode off-TPU, so the
                 backend is selectable everywhere.  Block sizes default to
                 ``None`` = resolved per call through the autotuner table
                 (kernels/tuning.py, DESIGN.md §11).
  * ``int8``   — quantized dense scan + float rerank tail: the corpus is
                 quantized ONCE at index build (``prepare_corpus`` →
                 :class:`QuantizedCorpus`, via
                 ``distributed/compression.quantize_int8``), queries are
                 quantized per call, the int8 Pallas kernel scans for the
                 top ``rerank_factor*k`` candidates on the raw integer dot
                 (ranking is invariant to the two global scales), and the
                 winners are exact-reranked in f32 — so results are
                 exact-at-k whenever the true top-k survives into the int8
                 top-``rerank_factor*k`` pool (DESIGN.md §11 for the
                 argument).  Hamming scoring delegates to the pallas
                 kernel (codes are already 1-bit); gathered scoring
                 delegates to the float pallas kernel (the ivfflat probe
                 gather has already shrunk the candidate set, so int8
                 would re-quantize per call for no bandwidth win).

``prepare_corpus`` is the build-time hook: engines pass their corpus-side
matrix through it so a backend can transform the layout once per index
(identity for jnp/pallas, quantization for int8).

Float scores are computed at full f32 matmul precision on every backend
(``lax.Precision.HIGHEST``; XLA's TPU default rounds f32 operands to bf16),
so the backends rank alike on the chip as they do on the CPU.

Tie policy (both backends, verified by tests/test_search_core.py): results
are score-descending; equal scores break toward the FIRST candidate in the
input layout (``lax.top_k`` takes the first occurrence, and the kernels'
per-block extraction + ascending-block merge preserve the same order).
For ``topk`` and ``hamming_topk`` the layout is id-ascending, so ties
break toward the lower candidate id; for ``gathered_topk`` the layout is
the caller's candidate order (for ivfflat: probe rank × slot), so ties
break by candidate *position*, not id.  Misses — k larger than the
candidate count, or invalid slots — come back as score −inf / id −1.

Backends are frozen dataclasses so callers can tune block sizes with
``dataclasses.replace`` without mutating the registry's shared instance.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.compression import quantize_int8
from repro.kernels.lsh_hamming import ops as lsh_ops
from repro.kernels.lsh_hamming.ref import hamming_topk_ref_t
from repro.kernels.topk_scoring import ops as topk_ops
from repro.kernels.topk_scoring.ref import gathered_topk_ref
from repro.kernels.topk_scoring.ref import pad_topk as _pad_topk


@runtime_checkable
class ScoringBackend(Protocol):
    """Execution strategy for the three scoring primitives."""

    name: str

    def prepare_corpus(self, vecs: jnp.ndarray):
        """Build-time hook: corpus f32[N, D] -> whatever layout ``topk``
        consumes (identity for float backends)."""
        ...

    def topk(self, queries: jnp.ndarray, corpus, *, k: int, n_valid=None):
        """(Q, D) x prepared corpus -> (scores f32[Q, k], ids i32[Q, k]).
        Rows at or past ``n_valid`` (an int or a traced int32 scalar; every
        row by default) are not candidates: a row-sharded corpus's zero pad
        rows, which would otherwise outscore genuinely negative rows."""
        ...

    def hamming_topk(self, q_codes: jnp.ndarray, c_codes_t: jnp.ndarray, *,
                     k: int):
        """Packed codes (Q, W) x the index's transposed codes (W, N) ->
        (−distance f32[Q, k], ids)."""
        ...

    def gathered_topk(self, queries: jnp.ndarray, cand_vecs: jnp.ndarray,
                      cand_ids: jnp.ndarray, *, k: int):
        """(Q, D) x (Q, C, D) with ids (Q, C), −1 = invalid slot."""
        ...


_REGISTRY: Dict[str, ScoringBackend] = {}


def register_backend(cls):
    """Class decorator: instantiate and register a backend under its name."""
    backend = cls()
    _REGISTRY[backend.name] = backend
    return cls


def get_backend(name: str) -> ScoringBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scoring backend {name!r}; registered backends: "
            f"{', '.join(available_backends())}") from None


def available_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


class QuantizedCorpus(NamedTuple):
    """Int8-quantized corpus built once per index (``prepare_corpus``):
    codes for the kernel scan, the global scale, and the original float
    vectors kept for the exact rerank tail."""

    codes: jnp.ndarray   # (N, D) int8
    scale: jnp.ndarray   # () f32 global max-abs scale
    vecs: jnp.ndarray    # (N, D) f32 originals (rerank + float fallback)


def _float_corpus(corpus) -> jnp.ndarray:
    """Float view of a prepared corpus — lets the float backends search an
    index an int8-backed engine built (cross-backend ``dataclasses.replace``
    swaps stay valid)."""
    return corpus.vecs if isinstance(corpus, QuantizedCorpus) else corpus


def rerank_candidates(vecs: jnp.ndarray, queries: jnp.ndarray,
                      cand: jnp.ndarray, *, k: int):
    """Exact inner-product rerank of per-query candidate ids (−1 = miss):
    (Q, R) -> top-k (scores, ids).  Shared by the single-device and sharded
    lsh search paths and the int8 backend's float tail, so all rank
    identically."""
    cvecs = vecs[jnp.maximum(cand, 0)]                    # (Q, R, d)
    s = jnp.einsum("qd,qrd->qr", queries, cvecs,
                   precision=lax.Precision.HIGHEST)
    s = jnp.where(cand >= 0, s, -jnp.inf)
    top_s, pos = lax.top_k(s, min(k, cand.shape[1]))
    top_i = jnp.take_along_axis(cand, pos, axis=1)
    top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
    return _pad_topk(top_s, top_i, k)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _blocked_topk(queries: jnp.ndarray, corpus: jnp.ndarray, n_valid, *,
                  k: int, block: int = 4096):
    """Streaming blocked top-k: candidates scored block-by-block with a
    running merge, so the (Q, N) score matrix never materialises — the same
    structure the Pallas topk_scoring kernel implements in VMEM.  Handles
    k > N natively (the −inf/−1 init survives into the output).  The corpus
    is read in place, never padded: a ragged last block starts early, at
    N − block, and masks the rows the block before it scored; rows at or
    past ``n_valid`` are masked likewise."""
    qn, d = queries.shape
    n = corpus.shape[0]
    block = max(1, min(block, n))
    nb = (n + block - 1) // block

    def step(carry, bi):
        best_s, best_i = carry
        lo = jnp.minimum(bi * block, n - block)
        blk = lax.dynamic_slice_in_dim(corpus, lo, block)
        s = jnp.dot(queries, blk.T, precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)        # (Q, block)
        ids = lo + jnp.arange(block, dtype=jnp.int32)[None]
        valid = (ids >= bi * block) & (ids < n_valid)
        s = jnp.where(valid, s, -jnp.inf)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], 1)
        top_s, pos = lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return (top_s, top_i), None

    init = (jnp.full((qn, k), -jnp.inf, jnp.float32),
            jnp.full((qn, k), -1, jnp.int32))
    (scores, ids), _ = lax.scan(step, init,
                                jnp.arange(nb, dtype=jnp.int32))
    return scores, jnp.where(jnp.isfinite(scores), ids, -1)


@register_backend
@dataclasses.dataclass(frozen=True)
class JnpBackend:
    """Pure-XLA reference backend (the oracle the pallas backend is tested
    against)."""

    block: int = 4096
    name: str = "jnp"

    def prepare_corpus(self, vecs):
        return vecs

    def topk(self, queries, corpus, *, k: int, n_valid=None):
        corpus = _float_corpus(corpus)
        return _blocked_topk(queries, corpus,
                             corpus.shape[0] if n_valid is None else n_valid,
                             k=k, block=self.block)

    def hamming_topk(self, q_codes, c_codes_t, *, k: int):
        k_eff = min(k, c_codes_t.shape[1])
        return _pad_topk(*hamming_topk_ref_t(q_codes, c_codes_t, k=k_eff), k)

    def gathered_topk(self, queries, cand_vecs, cand_ids, *, k: int):
        k_eff = min(k, cand_ids.shape[1])
        return _pad_topk(
            *gathered_topk_ref(queries, cand_vecs, cand_ids, k=k_eff), k)


@register_backend
@dataclasses.dataclass(frozen=True)
class PallasBackend:
    """Fused Pallas kernels (interpret mode off-TPU); the dispatch wrappers
    in kernels/*/ops.py own padding and k-clamping.

    ``None`` block fields defer to the autotuner table (kernels/tuning.py):
    explicit kwarg > tuned entry for the corpus-size bucket > hard-coded
    default.  ``dataclasses.replace`` with concrete ints pins blocks."""

    block_q: Optional[int] = None
    block_n: Optional[int] = None
    block_c: Optional[int] = None
    name: str = "pallas"

    def prepare_corpus(self, vecs):
        return vecs

    def topk(self, queries, corpus, *, k: int, n_valid=None):
        return topk_ops.topk_scores(queries, _float_corpus(corpus), k=k,
                                    n_valid=n_valid, block_q=self.block_q,
                                    block_n=self.block_n)

    def hamming_topk(self, q_codes, c_codes_t, *, k: int):
        return lsh_ops.hamming_topk_t(q_codes, c_codes_t, k=k,
                                      block_q=self.block_q,
                                      block_n=self.block_n)

    def gathered_topk(self, queries, cand_vecs, cand_ids, *, k: int):
        return topk_ops.gathered_topk(queries, cand_vecs, cand_ids, k=k,
                                      block_c=self.block_c)


@register_backend
@dataclasses.dataclass(frozen=True)
class Int8Backend:
    """Quantized dense scan + float rerank tail.

    The int8 kernel scans the quantized corpus for the top
    ``rerank_factor*k`` candidates on the raw integer dot (scale-invariant
    ranking: both scales are global positive constants), then
    :func:`rerank_candidates` rescores those candidates with the original
    f32 vectors — exact-at-k whenever the true top-k survives into the
    int8 candidate pool (rerank_factor trades recall against scan width;
    ``eval/fidelity.backend_recall_curve`` measures the trade).

    Hamming/gathered scoring delegate to the pallas kernels — codes are
    already 1-bit, and the ivfflat probe gather has already shrunk the
    candidate set, so a per-call re-quantization buys no bandwidth."""

    rerank_factor: int = 4
    block_q: Optional[int] = None
    block_n: Optional[int] = None
    name: str = "int8"

    def prepare_corpus(self, vecs):
        vecs = jnp.asarray(vecs)
        codes, scale = quantize_int8(vecs)
        return QuantizedCorpus(codes, scale, vecs)

    def topk(self, queries, corpus, *, k: int, n_valid=None):
        qc = (corpus if isinstance(corpus, QuantizedCorpus)
              else self.prepare_corpus(corpus))
        n = qc.codes.shape[0]
        pool = min(max(self.rerank_factor * k, k), n)
        q_codes, _ = quantize_int8(jnp.asarray(queries, jnp.float32))
        _, cand = topk_ops.topk_scores_int8(q_codes, qc.codes, k=pool,
                                            n_valid=n_valid,
                                            block_q=self.block_q,
                                            block_n=self.block_n)
        return rerank_candidates(qc.vecs, queries, cand, k=k)

    def hamming_topk(self, q_codes, c_codes_t, *, k: int):
        return lsh_ops.hamming_topk_t(q_codes, c_codes_t, k=k,
                                      block_q=self.block_q,
                                      block_n=self.block_n)

    def gathered_topk(self, queries, cand_vecs, cand_ids, *, k: int):
        return topk_ops.gathered_topk(queries, cand_vecs, cand_ids, k=k)
