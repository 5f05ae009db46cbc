"""GraphBuilder — Algorithm 1 of the WindTunnel paper.

Builds the weighted entity-affinity graph from a QRel table:

  Step 1 (map):    keep (q, e, s) with s > tau.
  Step 1 (reduce): for every query, emit every entity pair (e1 < e2) that
                   shares it, with affinity S = min(qrel(q,e1), qrel(q,e2)).
  Step 2:          dedup pairs keeping the MAX affinity.

MapReduce -> JAX mapping (DESIGN.md §2): the reduce-by-query self-join is a
degree-capped ELL expansion — QRels are sorted by (query, -score), the top
``fanout`` entities per query form a dense (num_queries, fanout) table, and
pair enumeration is a static (fanout choose 2) broadcast. The cap plays the
same role as the paper's top-50%-score filter: it bounds the O(K^2) pair
blow-up. Dedup is sort + segment_max.

Everything is static-shape and jit-able.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from repro.core import segment_utils as su


class QRelTable(NamedTuple):
    """Padded relational QRel table: (entity_id, query_id, score) rows."""
    query_ids: jnp.ndarray   # i32[n]
    entity_ids: jnp.ndarray  # i32[n]
    scores: jnp.ndarray      # f32[n]
    valid: jnp.ndarray       # bool[n]


class EdgeList(NamedTuple):
    """Padded undirected weighted edge list (u < v canonical)."""
    u: jnp.ndarray      # i32[m]
    v: jnp.ndarray      # i32[m]
    w: jnp.ndarray      # f32[m]
    valid: jnp.ndarray  # bool[m]

    @property
    def num_valid(self):
        return jnp.sum(self.valid.astype(jnp.int32))


def threshold_tau(qrels: QRelTable, tau_quantile: float) -> jnp.ndarray:
    """Score value such that scores strictly above it survive Step 1.

    The paper filters 'rankings with scores in the top 50%'; we express tau
    as a quantile of the valid scores so the same config works on any corpus.
    """
    s = jnp.where(qrels.valid, qrels.scores, jnp.nan)
    return jnp.nanquantile(s, tau_quantile)


def filter_qrels(qrels: QRelTable, tau: jnp.ndarray) -> QRelTable:
    """Step 1 map phase: Emit (q, (e, s)) if s > tau."""
    keep = qrels.valid & (qrels.scores > tau)
    return QRelTable(qrels.query_ids, qrels.entity_ids, qrels.scores, keep)


def build_ell(qrels: QRelTable, num_queries: int, fanout: int):
    """Group QRels by query into a dense ELL table of the top-``fanout``
    entities per query (by score).

    Returns (ell_e i32[num_queries, fanout] with -1 padding,
             ell_s f32[num_queries, fanout]).
    """
    qk = jnp.where(qrels.valid, qrels.query_ids, su.I32_MAX)
    neg_s = jnp.where(qrels.valid, -qrels.scores, jnp.inf)
    (qs, ss), (es, vs) = su.sort_by(
        (qk, neg_s), (qrels.entity_ids, qrels.valid.astype(jnp.int32)))
    starts = su.run_starts(qs)
    rank = su.group_rank(starts)
    ok = (vs == 1) & (rank < fanout) & (qs < num_queries)
    row = jnp.where(ok, qs, num_queries)  # out-of-bounds rows are dropped
    col = jnp.where(ok, rank, 0)
    ell_e = jnp.full((num_queries, fanout), -1, jnp.int32)
    ell_e = ell_e.at[row, col].set(es.astype(jnp.int32), mode="drop")
    ell_s = jnp.zeros((num_queries, fanout), jnp.float32)
    ell_s = ell_s.at[row, col].set(-ss, mode="drop")
    return ell_e, ell_s


def affinity_pairs(ell_e: jnp.ndarray, ell_s: jnp.ndarray) -> EdgeList:
    """Step 1 reduce phase: enumerate entity pairs sharing a query.

    S_affinity = min(qrel(q, e1), qrel(q, e2)) along the 2-hop path
    (e1 -> q -> e2). Canonical orientation u < v.
    """
    fanout = ell_e.shape[1]
    iu, ju = jnp.triu_indices(fanout, k=1)
    ea, eb = ell_e[:, iu], ell_e[:, ju]           # (Q, P)
    sa, sb = ell_s[:, iu], ell_s[:, ju]
    valid = (ea >= 0) & (eb >= 0) & (ea != eb)
    u = jnp.minimum(ea, eb)
    v = jnp.maximum(ea, eb)
    w = jnp.minimum(sa, sb)
    return EdgeList(u.ravel(), v.ravel(), w.ravel(), valid.ravel())


def dedup_edges(edges: EdgeList) -> EdgeList:
    """Step 2: one edge per (u, v) pair, keeping max affinity.

    Output is aligned to run-starts of the (u, v)-sorted order; non-start
    positions are masked out.  Invalid input rows sort last under
    ``I32_MAX`` keys, so every valid output edge lies within the first
    (number of valid input rows) slots: for :func:`affinity_pairs` over a
    table of ``rows`` judgments, within rows * (fanout - 1) // 2 slots,
    which label propagation slices to (``label_prop.lp_caps``).
    """
    n = edges.u.shape[0]
    uk = jnp.where(edges.valid, edges.u, su.I32_MAX)
    vk = jnp.where(edges.valid, edges.v, su.I32_MAX)
    (us, vs), (ws, vals) = su.sort_by((uk, vk), (edges.w, edges.valid.astype(jnp.int32)))
    starts = su.run_starts(us, vs)
    seg = su.run_segment_ids(starts)
    # max affinity per unique pair, broadcast back, representative = run start
    wmax = su.segment_max(jnp.where(vals == 1, ws, -jnp.inf), seg, num_segments=n)
    keep = starts & (vals == 1)
    return EdgeList(us, vs, wmax[seg], keep)


def build_affinity_graph(qrels: QRelTable, *, num_queries: int,
                         tau_quantile: float = 0.5, fanout: int = 16) -> EdgeList:
    """Full Algorithm 1: threshold -> ELL group-by -> pair gen -> dedup."""
    tau = threshold_tau(qrels, tau_quantile)
    kept = filter_qrels(qrels, tau)
    ell_e, ell_s = build_ell(kept, num_queries, fanout)
    pairs = affinity_pairs(ell_e, ell_s)
    return dedup_edges(pairs)


def symmetrize(edges: EdgeList) -> tuple:
    """Undirected edge list -> directed (src, dst, w, valid) with both
    orientations, for message passing."""
    src = jnp.concatenate([edges.u, edges.v])
    dst = jnp.concatenate([edges.v, edges.u])
    w = jnp.concatenate([edges.w, edges.w])
    valid = jnp.concatenate([edges.valid, edges.valid])
    return src, dst, w, valid


def node_degrees(edges: EdgeList, num_nodes: int) -> jnp.ndarray:
    """Node degree histogram support (Fig. 4 of the paper): the degree of an
    entity is its number of unique affinity-graph neighbours."""
    src, dst, _, valid = symmetrize(edges)
    ones = valid.astype(jnp.int32)
    deg = jnp.zeros((num_nodes,), jnp.int32).at[
        jnp.where(valid, dst, num_nodes)].add(ones, mode="drop")
    return deg
