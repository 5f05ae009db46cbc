"""Multi-device WindTunnel core: shard_map label propagation.

Node-sharded ELL layout: each device owns N/d nodes of the adjacency,
held slot-major (K, N/d) as the single-device engines hold it;
labels are the replicated carry. One round = local dense LP round (the
Pallas kernel's computation) + all_gather of the new local labels — one
collective per round, which is the distributed-LP communication lower bound
for bounded degree. Spark pays a full cluster shuffle per round; this is
the DESIGN.md §2 port at the multi-pod level.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.kernels.label_prop.ref import round_slot_major


def distributed_propagate_ell(mesh: Mesh, nbr: jnp.ndarray, wgt: jnp.ndarray,
                              *, rounds: int, axis: str = "data"):
    """nbr (N, K) i32 / wgt (N, K) f32, N divisible by mesh axis size,
    transposed once to the slot-major (K, N) layout.  Returns final labels
    (N,) i32 (replicated)."""
    n = nbr.shape[0]

    def local_rounds(nbr_l, wgt_l):
        # nbr_l/wgt_l: (K, N/d) local nodes; labels: (N,) replicated carry
        idx = lax.axis_index(axis)
        rows = nbr_l.shape[1]
        row0 = idx * rows

        def one(labels, _):
            local_own = lax.dynamic_slice(labels, (row0,), (rows,))
            lab = jnp.where(nbr_l >= 0, labels[jnp.maximum(nbr_l, 0)], -1)
            new_local = round_slot_major(lab, wgt_l, local_own)
            new_labels = lax.all_gather(new_local, axis, tiled=True)
            return new_labels, None

        labels0 = jnp.arange(n, dtype=jnp.int32)
        # the all-gathered carry is device-varying to shard_map's type
        # rule; the equal per-shard results collapse back with a pmax
        labels0 = lax.pcast(labels0, (axis,), to="varying")
        labels, _ = lax.scan(one, labels0, None, length=rounds)
        return lax.pmax(labels, (axis,))

    fn = shard_map(local_rounds, mesh=mesh,
                   in_specs=(P(None, axis), P(None, axis)),
                   out_specs=P())
    return fn(nbr.T, wgt.T)


def verify_against_single_device(mesh, nbr, wgt, rounds=3):
    """Test helper: distributed result == single-device ELL result."""
    from repro.core.label_prop import propagate_ell
    dist = distributed_propagate_ell(mesh, nbr, wgt, rounds=rounds)
    ref = propagate_ell(nbr, wgt, rounds=rounds).labels
    return jnp.array_equal(dist, ref)
