"""Data and plain reference of the ``msmarco-dense768-shard`` configuration.

Data: one chip's share of MS MARCO passages embedded at width 768, as
float32 rows drawn from a standard normal on the device in one jitted call
from the seed; queries likewise.

Reference: exact maximum-inner-product top-k on the host in NumPy,
independent of the program: float32 products over blocks of rows keep a
few dozen candidates per query, which are then scored again in float64 and
ranked (ties to the lower id).  A served answer is compared by

* ``topk_miss`` — queries whose top-k id set differs from the reference's,
  where the reference's k-th and (k+1)-th scores are not a near tie
  (within ``tie_rtol`` of the k-th);
* ``score_gap`` — the largest distance between a served score and the
  float64 inner product of the query with the row it names.

The control (:func:`control`) is the reference computed in the step below
the configuration's full float32: three bfloat16 passes (``high``).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _key(seed: int, stream: int) -> jax.Array:
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def _normal(key, *, rows: int, dim: int):
    return jax.random.normal(key, (rows, dim), jnp.float32)


def make_corpus(config: Dict, seed: int) -> jax.Array:
    """The corpus, f32[rows, dim], on the device."""
    return _normal(_key(seed, 0), rows=config["rows"], dim=config["dim"])


def make_queries(config: Dict, seed: int, n: int) -> np.ndarray:
    """``n`` queries, f32[n, dim], on the host."""
    return np.asarray(_normal(_key(seed, 1), rows=n, dim=config["dim"]))


def host_topk(corpus: np.ndarray, queries: np.ndarray, k: int, *,
              candidates: int = 64,
              block: int = 1 << 17) -> Tuple[np.ndarray, np.ndarray]:
    """Exact top-(k+1): (ids i64[Q, k+1], scores f64[Q, k+1]), best first."""
    q = np.asarray(queries, np.float32)
    cand = min(candidates, corpus.shape[0])
    best_s = np.full((q.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for lo in range(0, corpus.shape[0], block):
        s = q @ corpus[lo:lo + block].T
        s = np.concatenate([best_s, s], axis=1)
        ids = np.concatenate([best_i, np.broadcast_to(
            np.arange(lo, lo + s.shape[1] - best_s.shape[1]),
            (q.shape[0], s.shape[1] - best_s.shape[1]))], axis=1)
        top = np.argpartition(-s, cand - 1, axis=1)[:, :cand]
        best_s = np.take_along_axis(s, top, axis=1)
        best_i = np.take_along_axis(ids, top, axis=1)
    exact = np.einsum("qd,qcd->qc", q.astype(np.float64),
                      corpus[best_i].astype(np.float64))
    order = np.lexsort((best_i, -exact), axis=1)[:, :k + 1]
    return (np.take_along_axis(best_i, order, axis=1),
            np.take_along_axis(exact, order, axis=1))


def compare(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
            scores: np.ndarray, k: int, tie_rtol: float) -> Dict[str, float]:
    """``topk_miss`` and ``score_gap`` of served (scores, ids) f[Q, k]."""
    ref_ids, ref_s = host_topk(corpus, queries, k)
    miss = 0
    for i in range(queries.shape[0]):
        if set(ids[i, :k].tolist()) != set(ref_ids[i, :k].tolist()):
            sk, sk1 = ref_s[i, k - 1], ref_s[i, k]
            if abs(sk - sk1) > tie_rtol * abs(sk):
                miss += 1
    rows = corpus[np.clip(ids[:, :k], 0, None)].astype(np.float64)
    true = np.einsum("qd,qkd->qk", queries.astype(np.float64), rows)
    gap = np.where(ids[:, :k] >= 0,
                   np.abs(scores[:, :k].astype(np.float64) - true), np.inf)
    return {"topk_miss": float(miss), "score_gap": float(np.max(gap))}


def _split(x):
    """x = hi + lo + rest, hi and lo rounded to bfloat16 (kept in float32;
    ``reduce_precision`` is never folded away as excess precision)."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(x - hi, exponent_bits=8,
                                    mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("k",))
def _control_block(rows, queries, *, k: int):
    qh, ql = _split(queries)
    ch, cl = _split(rows)
    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
    return lax.top_k(dot(qh, ch.T) + dot(qh, cl.T) + dot(ql, ch.T), k)


def control_topk(corpus, queries, k: int,
                 block: int = 1 << 17) -> Tuple[np.ndarray, np.ndarray]:
    """The reference at ``high`` precision, put in the program's place:
    each product from three bfloat16 passes (hi·hi + hi·lo + lo·hi),
    summed in float32, over blocks of rows.  (scores, ids) f[Q, k]."""
    parts_s, parts_i = [], []
    for lo in range(0, corpus.shape[0], block):
        s, i = _control_block(corpus[lo:lo + block], jnp.asarray(queries),
                              k=k)
        parts_s.append(np.asarray(s))
        parts_i.append(np.asarray(i) + lo)
    s = np.concatenate(parts_s, axis=1)
    i = np.concatenate(parts_i, axis=1)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(i, order, axis=1))


def control(config: Dict, traffic: Dict, seed: int) -> Dict[str, float]:
    """The numbers compared when the ``high``-precision reference is put in
    the program's place, on as many of the traffic's queries as a run
    checks."""
    pool = traffic.get("queries", traffic.get("pool"))
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(pool, min(config["checked_answers"], pool),
                      replace=False)
    queries = make_queries(config, seed, pool)[pick]
    corpus = make_corpus(config, seed)
    scores, ids = control_topk(corpus, queries, traffic["k"])
    return compare(np.asarray(corpus), queries, ids, scores, traffic["k"],
                   config["tie_rtol"])
