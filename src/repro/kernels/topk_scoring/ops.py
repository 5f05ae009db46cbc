"""Dispatch wrappers for topk_scoring: pad the queries to block multiples
and select interpret mode off-TPU.  The kernels take any k (their partial
tiles widen in steps of 128 lanes); the jnp oracle runs only when a caller
asks for it with ``use_kernel=False``.

Shape contract (the engine path depends on it): any Q/N/C/k combination is
accepted — k is clamped to the candidate count, queries are padded to block
multiples (the corpus never is: the kernel masks a ragged last block and
any row past the caller's ``n_valid``), and missing results come back as
score −inf / id −1, so callers never see a ``lax.top_k`` shape error from
an undersized corpus.

Block sizes resolve through the autotuner table (kernels/tuning.py,
DESIGN.md §11): explicit kwarg > tuned entry for the corpus-size bucket >
hard-coded default.  Resolution happens in the plain-python outer wrappers,
BEFORE the inner jitted call — a lookup inside a jitted body would be baked
into the trace and go stale when the active table changes.  Blocks are also
clamped to the padded problem size (``_ceil8``), never floored up to a
128-wide block a small corpus then mostly wastes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.topk_scoring import ref
from repro.kernels.topk_scoring.ref import pad_topk as _pad_topk
from repro.kernels.topk_scoring.topk_scoring import (gathered_topk_pallas,
                                                     topk_scores_pallas)


def _ceil8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def topk_scores(queries: jnp.ndarray, corpus: jnp.ndarray, *, k: int,
                n_valid=None, block_q: int = None, block_n: int = None,
                use_kernel: bool = True):
    """Top-k inner-product search: (Q, D) x (N, D) -> (Q, k) scores/ids.
    Rows at or past ``n_valid`` (an int or a traced int32 scalar; all N
    rows by default) are not candidates."""
    n = corpus.shape[0]
    blocks = tuning.resolve("topk", n=n, dtype=queries.dtype,
                            block_q=block_q, block_n=block_n)
    return _topk_scores(queries, corpus, n if n_valid is None else n_valid,
                        k=k, use_kernel=use_kernel, **blocks)


def topk_scores_int8(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *, k: int,
                     n_valid=None, block_q: int = None, block_n: int = None,
                     use_kernel: bool = True):
    """Quantized top-k scan: int8 codes (Q, D) x (N, D) -> (Q, k) int-dot
    scores (as f32) and ids, rows at or past ``n_valid`` left out.
    Ranking is scale-invariant — dequantizing by the global query/corpus
    scales multiplies every score by the same positive constant — so
    callers rank on the raw dot and rerank the winners in float
    (retrieval/backends.py Int8Backend)."""
    n = c_codes.shape[0]
    blocks = tuning.resolve("topk", n=n, dtype="int8", block_q=block_q,
                            block_n=block_n)
    return _topk_scores(q_codes, c_codes, n if n_valid is None else n_valid,
                        k=k, use_kernel=use_kernel, **blocks)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "use_kernel"))
def _topk_scores(queries: jnp.ndarray, corpus: jnp.ndarray, n_valid, *,
                 k: int, block_q: int, block_n: int, use_kernel: bool):
    """f32 rows, or int8 codes on both sides.  Only the queries are padded
    (to a block multiple): the kernel bounds the corpus's rows itself, so
    the corpus is read where it lies and never copied."""
    n = corpus.shape[0]
    k_eff = min(k, n)
    if not use_kernel:
        oracle = (ref.topk_scores_int8_ref if corpus.dtype == jnp.int8
                  else ref.topk_scores_ref)
        s, i = oracle(queries, corpus, k=k_eff, n_valid=n_valid)
    else:
        qn = queries.shape[0]
        bq = min(block_q, max(8, qn))
        bn = min(block_n, _ceil8(n))
        qp = jnp.pad(queries.astype(corpus.dtype),
                     ((0, (-qn) % bq), (0, 0)))
        s, i = topk_scores_pallas(qp, corpus, n_valid, k=k_eff, block_q=bq,
                                  block_n=bn,
                                  interpret=tuning.interpret_mode())
        s, i = s[:qn], i[:qn]
    return _pad_topk(s, jnp.where(jnp.isfinite(s), i, -1), k)


def gathered_topk(queries: jnp.ndarray, cand_vecs: jnp.ndarray,
                  cand_ids: jnp.ndarray, *, k: int, block_q: int = None,
                  block_c: int = None, use_kernel: bool = True):
    """Per-query candidate top-k (the ivfflat probe-scoring step):
    queries (Q, D), cand_vecs (Q, C, D), cand_ids (Q, C) with −1 marking
    invalid slots -> (scores (Q, k), ids (Q, k)), −inf/−1 for misses."""
    blocks = tuning.resolve("gathered_topk", n=cand_vecs.shape[1],
                            dtype=queries.dtype, block_q=block_q,
                            block_c=block_c)
    return _gathered_topk(queries, cand_vecs, cand_ids, k=k,
                          use_kernel=use_kernel, **blocks)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_c",
                                             "use_kernel"))
def _gathered_topk(queries: jnp.ndarray, cand_vecs: jnp.ndarray,
                   cand_ids: jnp.ndarray, *, k: int, block_q: int,
                   block_c: int, use_kernel: bool):
    qn, d = queries.shape
    c = cand_vecs.shape[1]
    k_eff = min(k, c)
    if not use_kernel:
        return _pad_topk(
            *ref.gathered_topk_ref(queries, cand_vecs, cand_ids, k=k_eff), k)
    bq = _ceil8(min(block_q, qn))        # whole sublane tiles of queries
    bc = min(block_c, _ceil8(c))
    pad_q = (-qn) % bq
    pad_c = (-c) % bc
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 0)))
    cp = jnp.pad(cand_vecs.astype(jnp.float32),
                 ((0, pad_q), (0, pad_c), (0, 0)))
    ip = jnp.pad(cand_ids.astype(jnp.int32), ((0, pad_q), (0, pad_c)),
                 constant_values=-1)
    s, i = gathered_topk_pallas(qp, cp, ip, k=k_eff, block_q=bq, block_c=bc,
                                interpret=tuning.interpret_mode())
    return _pad_topk(s[:qn], i[:qn], k)
