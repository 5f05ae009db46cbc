"""Sign-random-projection LSH index (the paper cites LSH [3] as an index
option and Grale-style LSH graph building [4]).

Vectors hash to ``n_bits`` sign bits packed into int32 lanes; search ranks by
Hamming distance (XOR + popcount) with optional exact rerank of the top
candidates.  The Hamming scan dispatches through the scoring-backend
registry (retrieval/backends.py): ``jnp`` materialises the (Q, N) distance
matrix, ``pallas`` streams it through the kernels/lsh_hamming kernel.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.topk_scoring.ref import pad_topk  # noqa: F401 (re-export)
from repro.retrieval.backends import get_backend, rerank_candidates


class LSHIndex(NamedTuple):
    proj: jnp.ndarray    # (d, n_bits) random projection
    codes: jnp.ndarray   # (n_words, N) packed int32, transposed once at
                         # build so the Hamming kernel reads it as it is
    vecs: jnp.ndarray    # (N, d) kept for rerank


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """bits (..., n_bits) bool -> (..., n_bits/32) int32."""
    n_bits = bits.shape[-1]
    assert n_bits % 32 == 0
    b = bits.reshape(bits.shape[:-1] + (n_bits // 32, 32)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (b * weights).sum(-1).astype(jnp.int32)


def popcount32(x: jnp.ndarray) -> jnp.ndarray:
    """Branch-free popcount on int32 (as uint32 bit tricks)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def encode(proj: jnp.ndarray, vecs: jnp.ndarray) -> jnp.ndarray:
    # full f32 projection: XLA's TPU default (bf16 operands) would flip
    # the sign bits of near-zero projections
    return _pack_bits(jnp.dot(vecs, proj, precision=lax.Precision.HIGHEST)
                      > 0)


def build_lsh(key, corpus: jnp.ndarray, *, n_bits: int = 128) -> LSHIndex:
    d = corpus.shape[1]
    proj = jax.random.normal(key, (d, n_bits), corpus.dtype)
    return LSHIndex(proj, encode(proj, corpus).T, corpus)


@functools.partial(jax.jit, static_argnames=("k", "rerank", "backend"))
def search_lsh(index: LSHIndex, queries: jnp.ndarray, *, k: int,
               rerank: int = 0, backend: str = "jnp"):
    """Hamming-distance ANN; if ``rerank`` > 0, exact-rerank that many
    Hamming candidates with true inner products (higher score = better);
    with ``rerank`` <= 0 the first result is the POSITIVE Hamming distance
    (lower = better, +inf for misses), matching the historical API."""
    bk = get_backend(backend)
    qc = encode(index.proj, queries)                      # (Q, W)
    if rerank <= 0:
        neg, ids = bk.hamming_topk(qc, index.codes, k=k)
        return (-neg).astype(queries.dtype), ids
    _, cand = bk.hamming_topk(qc, index.codes, k=rerank)  # (Q, rerank)
    return rerank_candidates(index.vecs, queries, cand, k=k)
