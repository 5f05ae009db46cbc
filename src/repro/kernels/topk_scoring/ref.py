"""Pure-jnp oracle for the topk_scoring kernel.  Float scores use full
f32 matmul precision, as the kernels do (XLA's TPU default would round
the operands to bf16)."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def pad_topk(s: jnp.ndarray, i: jnp.ndarray, k: int):
    """Pad (Q, k_eff) top-k results back to (Q, k) with the miss
    convention every scoring path shares: score −inf, id −1.  The single
    definition of that convention — kernel dispatch wrappers, the backend
    registry and the sharded merge all import it."""
    k_eff = s.shape[1]
    if k_eff >= k:
        return s, i
    return (jnp.pad(s, ((0, 0), (0, k - k_eff)),
                    constant_values=-jnp.inf),
            jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1))


def _bounded_topk(scores: jnp.ndarray, k: int, n_valid):
    """Top-k of (Q, N) scores with columns at or past ``n_valid`` out."""
    if n_valid is not None:
        col = jnp.arange(scores.shape[1])[None, :]
        scores = jnp.where(col < n_valid, scores, -jnp.inf)
    top_s, top_i = lax.top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)


def topk_scores_ref(queries: jnp.ndarray, corpus: jnp.ndarray, *, k: int,
                    n_valid=None):
    scores = jnp.dot(queries, corpus.T, precision=lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return _bounded_topk(scores, k, n_valid)


def topk_scores_int8_ref(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *,
                         k: int, n_valid=None):
    """int8-code oracle: exact int32 dot (|dot| ≤ 127²·D < 2³¹ for any
    realistic D), ranked as f32 like the kernel's partials."""
    scores = jnp.dot(q_codes.astype(jnp.int32), c_codes.astype(jnp.int32).T)
    return _bounded_topk(scores.astype(jnp.float32), k, n_valid)


def gathered_topk_ref(queries: jnp.ndarray, cand_vecs: jnp.ndarray,
                      cand_ids: jnp.ndarray, *, k: int):
    """Per-query candidate sets: queries (Q, D), cand_vecs (Q, C, D),
    cand_ids (Q, C) with −1 marking invalid slots -> top-k (scores, ids),
    invalid slots scored −inf and returned as id −1."""
    s = jnp.einsum("qd,qcd->qc", queries, cand_vecs,
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(cand_ids >= 0, s, -jnp.inf)
    top_s, pos = lax.top_k(s, k)
    top_i = jnp.take_along_axis(cand_ids, pos, axis=1).astype(jnp.int32)
    return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)
