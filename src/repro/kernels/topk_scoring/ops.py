"""Dispatch wrappers for topk_scoring: pad to block multiples and select
interpret mode off-TPU.  The kernels take any k (their partial tiles widen
in steps of 128 lanes); the jnp oracle runs only when a caller asks for it
with ``use_kernel=False``.

Shape contract (the engine path depends on it): any Q/N/C/k combination is
accepted — k is clamped to the candidate count, inputs are padded to block
multiples, and missing results come back as score −inf / id −1, so callers
never see a ``lax.top_k`` shape error from an undersized corpus.

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
                                                     topk_scores_int8_pallas,
                                                     topk_scores_pallas)


def _ceil8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def topk_scores(queries: jnp.ndarray, corpus: jnp.ndarray, *, k: int,
                block_q: int = None, block_n: int = None,
                use_kernel: bool = True):
    """Top-k inner-product search: (Q, D) x (N, D) -> (Q, k) scores/ids."""
    blocks = tuning.resolve("topk", n=corpus.shape[0], dtype=queries.dtype,
                            block_q=block_q, block_n=block_n)
    return _topk_scores(queries, corpus, k=k, use_kernel=use_kernel,
                        **blocks)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "use_kernel"))
def _topk_scores(queries: jnp.ndarray, corpus: jnp.ndarray, *, k: int,
                 block_q: int, block_n: int, use_kernel: bool):
    n = corpus.shape[0]
    k_eff = min(k, n)
    if not use_kernel:
        return _pad_topk(*ref.topk_scores_ref(queries, corpus, k=k_eff), k)
    qn, d = queries.shape
    bq = min(block_q, max(8, qn))
    bn = min(block_n, _ceil8(n))
    pad_q = (-qn) % bq
    pad_n = (-n) % bn
    # sentinel coordinate: query coord 1, real candidates 0, padding -BIG —
    # padded rows then score -BIG and can never displace real candidates
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 1)),
                 constant_values=1.0)
    qp = qp.at[:, d].set(1.0)
    cp = jnp.pad(corpus.astype(jnp.float32), ((0, pad_n), (0, 1)))
    if pad_n:
        cp = cp.at[n:, d].set(-1e30)
    s, i = topk_scores_pallas(qp, cp, k=k_eff, block_q=bq, block_n=bn,
                              interpret=tuning.interpret_mode())
    if pad_n:
        bad = i >= n
        s = jnp.where(bad, -jnp.inf, s)
        i = jnp.where(bad, -1, i)
    return _pad_topk(s[:qn], i[:qn], k)


def topk_scores_int8(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *, k: int,
                     block_q: int = None, block_n: int = None,
                     use_kernel: bool = True):
    """Quantized top-k scan: int8 codes (Q, D) x (N, D) -> (Q, k) int-dot
    scores (as f32) and ids.  Ranking is scale-invariant — dequantizing by
    the global query/corpus scales multiplies every score by the same
    positive constant — so callers rank on the raw dot and rerank the
    winners in float (retrieval/backends.py Int8Backend)."""
    blocks = tuning.resolve("topk", n=c_codes.shape[0], dtype="int8",
                            block_q=block_q, block_n=block_n)
    return _topk_scores_int8(q_codes, c_codes, k=k, use_kernel=use_kernel,
                             **blocks)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "use_kernel"))
def _topk_scores_int8(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *, k: int,
                      block_q: int, block_n: int, use_kernel: bool):
    n = c_codes.shape[0]
    k_eff = min(k, n)
    if not use_kernel:
        return _pad_topk(
            *ref.topk_scores_int8_ref(q_codes, c_codes, k=k_eff), k)
    qn = q_codes.shape[0]
    bq = min(block_q, max(8, qn))
    bn = min(block_n, _ceil8(n))
    pad_q = (-qn) % bq
    pad_n = (-n) % bn
    # zero-padding only: padded rows are masked by n_valid INSIDE the
    # kernel (the lsh scheme) — an int8 sentinel coordinate can't dominate
    qp = jnp.pad(q_codes, ((0, pad_q), (0, 0)))
    cp = jnp.pad(c_codes, ((0, pad_n), (0, 0)))
    s, i = topk_scores_int8_pallas(qp, cp, k=k_eff, block_q=bq, block_n=bn,
                                   interpret=tuning.interpret_mode(),
                                   n_valid=n)
    if pad_n:
        bad = i >= n
        s = jnp.where(bad, -jnp.inf, s)
        i = jnp.where(bad, -1, i)
    return _pad_topk(s[:qn], i[:qn], k)


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
