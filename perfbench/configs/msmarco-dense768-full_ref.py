"""Data and plain reference of the ``msmarco-dense768-full`` configuration:
all of MS MARCO passages at width 768, row-sharded over the four chips of
one host.  The configuration's own copy of the dense reference.

Data: MS MARCO passages embedded at width 768, as float32 rows drawn from
a standard normal on the device in one jitted call from the seed; queries
likewise.  On several devices the corpus is born row-sharded over them,
ceil(rows / d) rows a device (2,210,456 of 8,841,823 on four), the rows
past ``rows`` zero: the draw is partitioned, each device drawing its own
shard, so no device ever holds the whole; on one device it is one array.

Reference: exact maximum-inner-product top-k on the host in NumPy,
independent of the program: float32 products over blocks of rows keep a
few dozen candidates per query, which are then scored again in float64 and
ranked (ties to the lower id).  It reads the corpus the run made, shard by
shard and block by block, and gathers the rows it scores again, so the
host never holds the whole corpus.  A served answer is compared by

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
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _key(seed: int, stream: int) -> jax.Array:
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def _normal(key, *, rows: int, dim: int):
    return jax.random.normal(key, (rows, dim), jnp.float32)


@functools.partial(jax.jit, static_argnames=("rows", "shape", "sharding"))
def _normal_rows(key, *, rows: int, shape: Tuple[int, int], sharding):
    """f32[shape] placed by ``sharding``: the first ``rows`` rows are those
    :func:`_normal` draws (an element's random bits depend only on its flat
    index), the rest zero."""
    x = jax.random.normal(key, shape, jnp.float32)
    x = jnp.where(lax.broadcasted_iota(jnp.int32, shape, 0) < rows, x, 0.0)
    return lax.with_sharding_constraint(x, sharding)


def make_corpus(config: Dict, seed: int, devices: Sequence) -> jax.Array:
    """The corpus on ``devices``: f32[rows, dim] on one device, or row-
    sharded over several as f32[ceil(rows / d) * d, dim], zero past
    ``rows``."""
    rows, dim = config["rows"], config["dim"]
    if len(devices) == 1:
        with jax.default_device(devices[0]):
            return _normal(_key(seed, 0), rows=rows, dim=dim)
    d = len(devices)
    sharding = NamedSharding(Mesh(np.asarray(devices), ("rows",)),
                             P("rows", None))
    return _normal_rows(_key(seed, 0), rows=rows,
                        shape=(-(-rows // d) * d, dim), sharding=sharding)


def make_queries(config: Dict, seed: int, n: int) -> np.ndarray:
    """``n`` queries, f32[n, dim], on the host."""
    return np.asarray(_normal(_key(seed, 1), rows=n, dim=config["dim"]))


def _shards(corpus: jax.Array) -> List[Tuple[int, jax.Array]]:
    """(first row, rows) of each distinct shard of ``corpus``, in row
    order, each on its own device."""
    blocks = {}
    for shard in corpus.addressable_shards:
        blocks.setdefault(shard.index[0].start or 0, shard.data)
    return sorted(blocks.items(), key=lambda b: b[0])


def _blocks(corpus: jax.Array, n: int, block: int):
    """(first row, rows f32[<= block, D] on the host) over the first ``n``
    rows, block by block within each shard."""
    for start, data in _shards(corpus):
        stop = min(data.shape[0], n - start)
        for lo in range(0, stop, block):
            yield start + lo, np.asarray(data[lo:min(lo + block, stop)])


def _gather(corpus: jax.Array, ids: np.ndarray) -> np.ndarray:
    """Rows ``ids`` (every id below the corpus's rows) of ``corpus``, f32 on
    the host, each read from the shard that holds it."""
    ids = np.asarray(ids)
    out = np.zeros(ids.shape + (corpus.shape[1],), np.float32)
    for start, data in _shards(corpus):
        own = (ids >= start) & (ids < start + data.shape[0])
        if own.any():
            out[own] = np.asarray(data[(ids[own] - start).astype(np.int32)])
    return out


def host_topk(corpus: jax.Array, n: int, queries: np.ndarray, k: int, *,
              candidates: int = 64,
              block: int = 1 << 17) -> Tuple[np.ndarray, np.ndarray]:
    """Exact top-(k+1) over the first ``n`` rows of ``corpus``: (ids
    i64[Q, k+1], scores f64[Q, k+1]), best first."""
    q = np.asarray(queries, np.float32)
    cand = min(candidates, n)
    best_s = np.full((q.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for lo, rows in _blocks(corpus, n, block):
        s = q @ rows.T
        s = np.concatenate([best_s, s], axis=1)
        ids = np.concatenate([best_i, np.broadcast_to(
            np.arange(lo, lo + s.shape[1] - best_s.shape[1]),
            (q.shape[0], s.shape[1] - best_s.shape[1]))], axis=1)
        top = np.argpartition(-s, cand - 1, axis=1)[:, :cand]
        best_s = np.take_along_axis(s, top, axis=1)
        best_i = np.take_along_axis(ids, top, axis=1)
    exact = np.einsum("qd,qcd->qc", q.astype(np.float64),
                      _gather(corpus, best_i).astype(np.float64))
    order = np.lexsort((best_i, -exact), axis=1)[:, :k + 1]
    return (np.take_along_axis(best_i, order, axis=1),
            np.take_along_axis(exact, order, axis=1))


def compare(config: Dict, corpus: jax.Array, queries: np.ndarray,
            ids: np.ndarray, scores: np.ndarray, k: int) -> Dict[str, float]:
    """``topk_miss`` and ``score_gap`` of served (scores, ids) f[Q, k]
    against the corpus that :func:`make_corpus` made."""
    n, tie_rtol = config["rows"], config["tie_rtol"]
    ref_ids, ref_s = host_topk(corpus, n, queries, k)
    miss = 0
    for i in range(queries.shape[0]):
        if set(ids[i, :k].tolist()) != set(ref_ids[i, :k].tolist()):
            sk, sk1 = ref_s[i, k - 1], ref_s[i, k]
            if abs(sk - sk1) > tie_rtol * abs(sk):
                miss += 1
    named = np.clip(ids[:, :k], 0, n - 1)
    rows = _gather(corpus, named).astype(np.float64)
    true = np.einsum("qd,qkd->qk", queries.astype(np.float64), rows)
    gap = np.where((ids[:, :k] >= 0) & (ids[:, :k] < n),
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


def control_topk(corpus: jax.Array, n: int, queries, k: int,
                 block: int = 1 << 17) -> Tuple[np.ndarray, np.ndarray]:
    """The reference at ``high`` precision, put in the program's place:
    each product from three bfloat16 passes (hi·hi + hi·lo + lo·hi),
    summed in float32, over blocks of the first ``n`` rows, each on the
    device that holds it.  (scores, ids) f[Q, k]."""
    parts_s, parts_i = [], []
    for start, data in _shards(corpus):
        stop = min(data.shape[0], n - start)
        q = jax.device_put(np.asarray(queries), data.sharding)
        for lo in range(0, stop, block):
            s, i = _control_block(data[lo:min(lo + block, stop)], q, k=k)
            parts_s.append(np.asarray(s))
            parts_i.append(np.asarray(i) + start + lo)
    s = np.concatenate(parts_s, axis=1)
    i = np.concatenate(parts_i, axis=1)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(i, order, axis=1))


def control(config: Dict, traffic: Dict, seed: int,
            devices: Sequence) -> Dict[str, float]:
    """The numbers compared when the ``high``-precision reference is put in
    the program's place, on as many of the traffic's queries as a run
    checks, over the corpus as the cell makes it on ``devices``."""
    pool = traffic.get("queries", traffic.get("pool"))
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(pool, min(config["checked_answers"], pool),
                      replace=False)
    queries = make_queries(config, seed, pool)[pick]
    corpus = make_corpus(config, seed, devices)
    scores, ids = control_topk(corpus, config["rows"], queries, traffic["k"])
    return compare(config, corpus, queries, ids, scores, traffic["k"])
