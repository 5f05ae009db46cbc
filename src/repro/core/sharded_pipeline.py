"""Sharded WindTunnel pipeline — the single-device dataflow of pipeline.py
partitioned across a device mesh with ``shard_map`` (DESIGN.md §5).

Dataflow (one XLA program, one ``shard_map`` region):

  1. **Query-partitioned GraphBuilder.**  The (tau-filtered) QRel table is
     routed so that each device owns a contiguous block of query ids, then
     each device builds its per-shard ELL table and enumerates affinity
     pairs locally — the reduce-by-query self-join never leaves the shard
     because a query's rows are never split.
  2. **Edge merge.**  The per-shard pair lists are concatenated with a tiled
     all-gather and deduplicated with the same sort + segment-max reduction
     the single-device path uses (collectives.all_concat + gb.dedup_edges):
     an all-gather + segment-max merge.
  3. **Node-partitioned label propagation.**  The merged edge list is packed
     into ELL adjacency rows for the local node block only (adjacency stays
     sharded, O(N·K/d) per device); the i32[N] label vector is the cheap
     replicated carry, refreshed by one label all-gather per round — the
     communication lower bound for bounded-degree distributed LP.
  4. **Sampling + reconstruction** run on the replicated outputs outside the
     shard_map region.  The cluster-sampling Bernoulli draw is keyed per
     label id (sampler.cluster_sample), so the sampled mask is a pure
     function of (seed, labels) — bit-identical to the single-device path
     on a 1-device mesh, and independent of the mesh shape given equal
     labels.

The LP round body follows ``config.engine``: ``ell`` (default) runs the
dense XLA round, ``pallas`` runs the Pallas kernel on the local node block
(interpret mode off-TPU).  The ``sort`` engine has no sharded formulation
(its per-round global sort is exactly the shuffle this path removes) —
selecting it here raises.

Padding invariants: queries are padded to a multiple of the shard count
(padded queries have no QRel rows), nodes to a multiple of the shard count
(padded nodes have no edges, keep their own label, and are sliced off
before sampling).  On a 1-device mesh both paddings are empty and every
stage is operation-for-operation the single-device program.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import graph_builder as gb
from repro.core import label_prop as lp
from repro.core import segment_utils as su
from repro.core.pipeline import WindTunnelConfig, WindTunnelResult
from repro.distributed import collectives as coll
from repro.distributed.sharded_corpus import ShardedQRels
from repro.distributed.sharding import GNN_RULES, partition_axes


def _mesh_axis_count(mesh: Mesh, axes: tuple) -> int:
    d = 1
    for a in axes:
        d *= mesh.shape[a]
    return d


def _route_by_query(qrels: gb.QRelTable, *, num_shards: int,
                    queries_per_shard: int) -> gb.QRelTable:
    """Partition QRel rows into per-shard buffers of shape (d, n): shard
    ``q // queries_per_shard`` owns every row of query q.  The stable sort
    preserves original row order within a shard, so each shard's local
    table is the compaction of its rows — downstream stable sorts see the
    same tie order as the single-device path."""
    n = qrels.query_ids.shape[0]
    shard = jnp.where(qrels.valid, qrels.query_ids // queries_per_shard,
                      num_shards)  # invalid rows route to the drop bucket
    (ss,), (q, e, s, v) = su.sort_by(
        (shard,), (qrels.query_ids, qrels.entity_ids, qrels.scores,
                   qrels.valid.astype(jnp.int32)))
    rank = su.group_rank(su.run_starts(ss))
    row = jnp.where(ss < num_shards, ss, num_shards)
    buf = lambda fill, dtype: jnp.full((num_shards, n), fill, dtype)
    q_b = buf(0, jnp.int32).at[row, rank].set(q.astype(jnp.int32), mode="drop")
    e_b = buf(0, jnp.int32).at[row, rank].set(e.astype(jnp.int32), mode="drop")
    s_b = buf(0.0, jnp.float32).at[row, rank].set(s, mode="drop")
    v_b = buf(0, jnp.int32).at[row, rank].set(v, mode="drop")
    return gb.QRelTable(q_b, e_b, s_b, v_b)


def _local_lp_round(nbr_labels_t, wgt_t, own, *, use_kernel: bool):
    """One LP round on a local node block with pre-gathered slot-major
    neighbour labels (K, rows) — either the jnp reference or the Pallas
    kernel (hot-loop winner), both bit-identical to label_prop.ell_round."""
    if not use_kernel:
        from repro.kernels.label_prop.ref import round_slot_major
        return round_slot_major(nbr_labels_t, wgt_t, own)
    from repro.kernels.label_prop.ops import pallas_round
    return pallas_round(nbr_labels_t, wgt_t, own)


def sharded_graph_and_labels(qrels, *, num_queries: int,
                             num_entities: int, config: WindTunnelConfig,
                             mesh: Mesh, axes: tuple = None) -> tuple:
    """Mesh-partitioned graph build + label propagation (stages 1-3 above):
    one ``shard_map`` region, returning ``(edges, degrees, labels,
    changes_per_round)`` — replicated, or row-sharded on the born path.

    ``qrels`` is either a global :class:`~repro.core.graph_builder.
    QRelTable` (tau-filtered and query-routed on device — the legacy flow,
    which materialises the full table on one device first) or a
    sharded-from-birth :class:`~repro.distributed.sharded_corpus.
    ShardedQRels` whose buffers were routed host-side and streamed straight
    to their shards.  On the born path tau is computed *inside* the mesh
    from an all-gather of the score column only (O(rows) scalars, never
    the table) — ``nanquantile`` is permutation-invariant, so the
    threshold is bit-identical to the global ``threshold_tau``.

    This is the expensive staged state of the sampling core
    (``sampling_core.SamplerSession``): sampling + reconstruction are cheap
    per-draw stages on the replicated outputs, identical to the
    single-device path.  ``axes`` defaults to the GNN sharding rule for
    node/query arrays filtered to the mesh (production: ('data', 'model');
    host mesh: the same names with total size 1).
    """
    if config.engine not in ("ell", "pallas"):
        raise ValueError(
            f"sharded pipeline requires an ELL-family engine ('ell' or "
            f"'pallas'); got {config.engine!r} — the sort engine's global "
            f"per-round shuffle is exactly what this path eliminates")
    born = isinstance(qrels, ShardedQRels)
    if born and axes is None:
        axes = qrels.axes
    if axes is None:
        axes = partition_axes(mesh, "nodes", GNN_RULES)
    axes = tuple(axes) if axes else ()
    if not axes:
        raise ValueError(f"mesh {mesh} has none of the GNN node axes")
    d = _mesh_axis_count(mesh, axes)

    qps = -(-num_queries // d)          # queries per shard (ceil)
    rows_n = -(-num_entities // d)      # nodes per shard (ceil)
    n_pad = rows_n * d
    if born:
        if qrels.num_shards != d or qrels.queries_per_shard != qps:
            raise ValueError(
                f"ShardedQRels routed for {qrels.num_shards} shards × "
                f"{qrels.queries_per_shard} queries/shard, but the mesh "
                f"needs {d} × {qps}")
        routed = gb.QRelTable(qrels.query_ids, qrels.entity_ids,
                              qrels.scores, qrels.valid)
    else:
        # Global tau: the only stage needing the full score distribution —
        # a scalar quantile, computed replicated before partitioning.
        tau = gb.threshold_tau(qrels, config.tau_quantile)
        kept = gb.filter_qrels(qrels, tau)
        routed = _route_by_query(kept, num_shards=d, queries_per_shard=qps)
    use_kernel = config.engine == "pallas"

    def shard_fn(q_b, e_b, s_b, v_b):
        # ---- local QRel block: (1, n) shard -> (n,) local table ----
        idx = coll.flat_axis_index(axes)
        valid = v_b[0].astype(bool)
        if born:
            # in-mesh tau over the gathered score COLUMN (scores only:
            # the table itself never leaves its shards); invalid/pad rows
            # mark NaN, which nanquantile ignores — same sorted valid
            # multiset as the global path, so tau is bit-identical
            marked = jnp.where(valid, s_b[0], jnp.nan)
            tau_l = jnp.nanquantile(
                lax.all_gather(marked, axes, axis=0, tiled=True),
                config.tau_quantile)
            valid = valid & (s_b[0] > tau_l)
        q_local = jnp.where(valid, q_b[0] - idx * qps, 0).astype(jnp.int32)
        local = gb.QRelTable(q_local, e_b[0], s_b[0], valid)

        # ---- Alg. 1 on the shard: ELL group-by + pair enumeration ----
        ell_e, ell_s = gb.build_ell(local, qps, config.fanout)
        pairs = gb.affinity_pairs(ell_e, ell_s)

        # ---- merge: all-gather pair lists, dedup with segment-max ----
        gathered = coll.all_concat(pairs, axes)
        edges = gb.dedup_edges(gathered)
        degrees = gb.node_degrees(edges, n_pad)
        src, dst, w, e_valid = gb.symmetrize(edges)

        # ---- node-partitioned ELL adjacency (local rows only) ----
        row0 = idx * rows_n
        dst_local = dst - row0
        mine = e_valid & (dst_local >= 0) & (dst_local < rows_n)
        nbr_l, wgt_l = lp.edges_to_ell_t(
            src, jnp.where(mine, dst_local, rows_n), w, mine,
            num_nodes=rows_n, max_degree=config.max_degree)

        # ---- LP rounds: sharded adjacency, replicated label carry ----
        def one(labels, _):
            own = lax.dynamic_slice(labels, (row0,), (rows_n,))
            lab = jnp.where(nbr_l >= 0, labels[jnp.maximum(nbr_l, 0)], -1)
            new = _local_lp_round(lab, wgt_l, own, use_kernel=use_kernel)
            changed = lax.psum(jnp.sum((new != own).astype(jnp.int32)), axes)
            return lax.all_gather(new, axes, tiled=True), changed

        labels, changes = lax.scan(one, jnp.arange(n_pad, dtype=jnp.int32),
                                   None, length=config.lp_rounds)
        if born:
            # Born outputs stay row-sharded: every shard computed the SAME
            # replicated edge/label values (dedup of an identical gather;
            # all-gathered label carry), so each keeps only its slice and
            # the assembled global array is bit-identical to the
            # replicated one — per-device residency drops from O(E + N)
            # to O((E + N) / d), which is what keeps the sampling bench's
            # peak_bytes_per_device flat under weak scaling.
            e_len = edges.u.shape[0] // d
            sl = lambda a: lax.dynamic_slice(a, (idx * e_len,), (e_len,))
            edges = gb.EdgeList(sl(edges.u), sl(edges.v),
                                sl(edges.w), sl(edges.valid))
            labels = lax.dynamic_slice(labels, (idx * rows_n,), (rows_n,))
            degrees = lax.dynamic_slice(degrees, (idx * rows_n,), (rows_n,))
        return edges, degrees, labels, changes

    shard_spec = P(axes if len(axes) > 1 else axes[0], None)
    row_spec = P(axes if len(axes) > 1 else axes[0])
    out_edge = (gb.EdgeList(*(row_spec,) * 4) if born
                else gb.EdgeList(P(), P(), P(), P()))
    node_spec = row_spec if born else P()
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(shard_spec,) * 4,
                   out_specs=(out_edge, node_spec, node_spec, P()),
                   check_vma=False)
    edges, degrees, labels, changes = fn(routed.query_ids, routed.entity_ids,
                                         routed.scores, routed.valid)
    return edges, degrees[:num_entities], labels[:num_entities], changes


def run_windtunnel_sharded(qrels: gb.QRelTable, *, num_queries: int,
                           num_entities: int, config: WindTunnelConfig,
                           mesh: Mesh, axes: tuple = None
                           ) -> WindTunnelResult:
    """Mesh-partitioned ``run_windtunnel`` with identical semantics.

    .. deprecated:: next release — thin wrapper over
       ``sampling_core.SamplerSession`` (``SamplerSpec(sharded=True,
       mesh=...)``), kept one release for existing callers.  The session
       amortizes the shard_map graph + LP stages across many draws; this
       wrapper re-stages them on every call.

    Sampling + reconstruction run on the replicated outputs (keyed per
    label id -> mesh-shape independent given equal labels), so a 1-device
    mesh is bit-identical to ``run_windtunnel``.
    """
    from repro.core.pipeline import note_deprecated
    from repro.core.sampling_core import SamplerSession, SamplerSpec
    note_deprecated("run_windtunnel_sharded",
                    "SamplerSession with SamplerSpec(sharded=True, mesh=...)")
    session = SamplerSession(
        qrels, num_queries=num_queries, num_entities=num_entities,
        spec=SamplerSpec.from_config(config, strategy="windtunnel",
                                     sharded=True, mesh=mesh, axes=axes))
    return session.result()
