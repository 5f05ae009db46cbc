"""GraphSampler steps 1-3 — weighted label propagation (Algorithm 2).

Paper semantics (Raghavan et al. [9], weighted variant):
  init:   L(v) = v
  round:  for each node v, over incident edges (v, u, w) aggregate
          S(L) = sum of w over neighbours u with label L;
          assign L*(v) = argmax_L S(L).
  stop:   after a fixed number of rounds (LP is not guaranteed to converge).

MapReduce -> JAX mapping: one round = one reduce-by-(dst, label) followed by
one reduce-by-dst argmax. Both are sort + segment ops (DESIGN.md §2); the
whole multi-round loop runs inside a single XLA computation via lax.scan
(Spark pays a cluster-wide shuffle per round; we pay an on-device sort).

Ties are broken toward the smaller label id — the paper leaves this
unspecified; a deterministic rule makes the pipeline reproducible.

``propagate_ell`` is the dense, degree-capped formulation that feeds the
Pallas label_prop kernel (kernels/label_prop) — same semantics, different
data layout (see ref.py there for the oracle correspondence).

The per-round functions here (``sort_round``, ``ell_round_t``) are the
building blocks the engine registry (engines.py, DESIGN.md §4) wraps into
uniformly selectable execution strategies.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import segment_utils as su
from repro.kernels.label_prop.ref import round_slot_major


class LabelPropResult(NamedTuple):
    labels: jnp.ndarray           # i32[num_nodes] final community labels
    changes_per_round: jnp.ndarray  # i32[rounds] nodes that changed label


def sort_round(labels, src, dst, w, valid, num_nodes):
    """One LP round over a directed edge list via sort + segment reduce —
    the round the ``sort`` engine (engines.SortEngine) executes."""
    e = src.shape[0]
    lab_src = labels[jnp.where(valid, src, 0)]
    dst_k = jnp.where(valid, dst, num_nodes)           # sentinel sorts last
    lab_k = jnp.where(valid, lab_src, su.I32_MAX)
    w_m = jnp.where(valid, w, 0.0)

    # reduce-by-(dst, label): sum of affinities per candidate label
    (dsts, labs), (ws,) = su.sort_by((dst_k, lab_k), (w_m,))
    starts = su.run_starts(dsts, labs)
    seg = su.run_segment_ids(starts)
    sums = su.segment_sum(ws, seg, num_segments=e)[seg]  # broadcast to rows

    # reduce-by-dst: argmax_L sum, tie -> min label
    dstarts = su.run_starts(dsts)
    dseg = su.run_segment_ids(dstarts)
    smax = su.segment_max(sums, dseg, num_segments=e)[dseg]
    cand = jnp.where(sums == smax, labs, su.I32_MAX)
    best = su.segment_min(cand, dseg, num_segments=e)

    # one representative row per dst-run; scatter back (sentinel rows drop)
    dst_of_seg = su.segment_min(dsts, dseg, num_segments=e)
    new_labels = labels.at[dst_of_seg].set(
        jnp.minimum(best, su.I32_MAX - 1).astype(labels.dtype), mode="drop")
    # runs made only of sentinel rows produce I32_MAX candidates; they were
    # dropped above because their dst is the sentinel num_nodes.
    return new_labels


def propagate(src, dst, w, valid, *, num_nodes: int, rounds: int) -> LabelPropResult:
    """Run ``rounds`` of weighted label propagation over a directed edge list.

    Use graph_builder.symmetrize() first for undirected graphs.
    """
    init = jnp.arange(num_nodes, dtype=jnp.int32)

    def step(labels, _):
        new = sort_round(labels, src, dst, w, valid, num_nodes)
        changed = jnp.sum((new != labels).astype(jnp.int32))
        return new, changed

    labels, changes = lax.scan(step, init, None, length=rounds)
    return LabelPropResult(labels, changes)


# ---------------------------------------------------------------------------
# Dense ELL formulation (feeds the Pallas kernel; also the vmap-able oracle)
# ---------------------------------------------------------------------------

def edges_to_ell_t(src, dst, w, valid, *, num_nodes: int, max_degree: int):
    """Pack a directed edge list into slot-major ELL adjacency:
    nbr_t i32[max_degree, num_nodes] (pad -1), wgt_t f32[max_degree,
    num_nodes] — nodes along the last (lane) dim, the layout the LP
    engines keep (a node-major (N, K) array with small K wastes most of
    its TPU tiles).

    Edges beyond ``max_degree`` per dst are dropped deterministically
    (highest-weight edges kept), mirroring the fanout cap of Alg. 1.
    """
    dst_k = jnp.where(valid, dst, num_nodes)
    negw = jnp.where(valid, -w, jnp.inf)
    (dsts, _), (srcs, ws) = su.sort_by((dst_k, negw), (src, w))
    starts = su.run_starts(dsts)
    rank = su.group_rank(starts)
    ok = (dsts < num_nodes) & (rank < max_degree)
    row = jnp.where(ok, dsts, num_nodes)
    col = jnp.where(ok, rank, 0)
    nbr = jnp.full((max_degree, num_nodes), -1, jnp.int32)
    nbr = nbr.at[col, row].set(srcs.astype(jnp.int32), mode="drop")
    wgt = jnp.zeros((max_degree, num_nodes), jnp.float32)
    wgt = wgt.at[col, row].set(ws, mode="drop")
    return nbr, wgt


def edges_to_ell(src, dst, w, valid, *, num_nodes: int, max_degree: int):
    """Node-major ELL adjacency: nbr i32[num_nodes, max_degree] (pad -1),
    wgt f32[num_nodes, max_degree] — :func:`edges_to_ell_t` transposed,
    for callers that hold (N, K) arrays."""
    nbr_t, wgt_t = edges_to_ell_t(src, dst, w, valid, num_nodes=num_nodes,
                                  max_degree=max_degree)
    return nbr_t.T, wgt_t.T


# ---------------------------------------------------------------------------
# Compaction: LP over the nodes that hold an edge
# ---------------------------------------------------------------------------

def lp_caps(*, rows: int, fanout: int, num_nodes: int,
            edge_slots: int) -> Tuple[int, int]:
    """Static bounds ``(node_cap, edge_cap)`` on the graph that Alg. 1
    builds from a judgment table of ``rows`` rows: ``node_cap`` nodes that
    can hold an edge, and ``edge_cap`` slots of the deduped edge list that
    can hold a valid one.

    A query whose top-``fanout`` keeps k' judgments emits C(k', 2) <=
    k' (fanout - 1) / 2 pairs, so at most rows (fanout - 1) // 2 pairs are
    valid, and ``dedup_edges`` puts them first.  A node with an edge is the
    entity of a judgment and an endpoint of a valid edge.
    """
    edge_cap = min(edge_slots, max(1, rows * (fanout - 1) // 2))
    return max(1, min(num_nodes, rows, 2 * edge_cap)), edge_cap


def compact_nodes(src, dst, valid, *, num_nodes: int, size: int):
    """Renumber the nodes that hold a valid edge of a symmetrized edge
    list 0, 1, ... in id order (each edge is a source once in either
    direction).

    Returns (src, dst) remapped (0 on invalid rows), ``ids`` i32[size]
    (compact node -> node id, ``num_nodes`` past the last) and the number
    of such nodes.  The map is strictly increasing, so label order, the
    min-label tie-break and every sort over (dst, ...) decide as on the
    original ids.  ``size`` must be at least the number of such nodes
    (:func:`lp_caps`).
    """
    mark = jnp.zeros((num_nodes,), bool).at[
        jnp.where(valid, src, num_nodes)].set(True, mode="drop")
    pos = jnp.cumsum(mark, dtype=jnp.int32) - 1
    ids = jnp.full((size,), num_nodes, jnp.int32).at[
        jnp.where(mark, pos, size)].set(
            jnp.arange(num_nodes, dtype=jnp.int32), mode="drop")

    def remap(x):
        return jnp.where(valid, pos[jnp.where(valid, x, 0)], 0)

    return remap(src), remap(dst), ids, pos[-1] + 1


def expand_labels(labels, ids, *, num_nodes: int):
    """Compact labels back to i32[num_nodes]: node ``ids[i]`` takes label
    ``ids[labels[i]]``; every other node keeps its own id."""
    return jnp.arange(num_nodes, dtype=jnp.int32).at[ids].set(
        ids[labels], mode="drop")


def ell_round_t(labels, nbr_t, wgt_t):
    """One LP round over slot-major ELL adjacency (K, N) — the ``ell``
    engine's round, and the computation the Pallas kernel implements."""
    lab = jnp.where(nbr_t >= 0, labels[jnp.maximum(nbr_t, 0)], -1)
    return round_slot_major(lab, wgt_t, labels).astype(labels.dtype)


def ell_round(labels, nbr, wgt):
    """One LP round over node-major ELL adjacency (N, K): transposes it to
    :func:`ell_round_t` on every call.  O(N * K^2) but fully dense.

    For node n with neighbour labels l_k and weights w_k:
      S(l_j) = sum_k w_k [l_k == l_j];  L* = argmax_j (S, -l_j).
    Nodes with no neighbours keep their label.  Scores accumulate in the
    kernel's fixed slot order (kernels/label_prop ``same_label_scores``),
    so this engine and the ``pallas`` engine agree bit for bit.
    """
    return ell_round_t(labels, nbr.T, wgt.T)


def propagate_ell(nbr, wgt, *, rounds: int) -> LabelPropResult:
    """``rounds`` of LP over node-major ELL adjacency (N, K), run
    slot-major (one transpose up front)."""
    num_nodes = nbr.shape[0]
    init = jnp.arange(num_nodes, dtype=jnp.int32)
    nbr_t, wgt_t = nbr.T, wgt.T

    def step(labels, _):
        new = ell_round_t(labels, nbr_t, wgt_t)
        return new, jnp.sum((new != labels).astype(jnp.int32))

    labels, changes = lax.scan(step, init, None, length=rounds)
    return LabelPropResult(labels, changes)
