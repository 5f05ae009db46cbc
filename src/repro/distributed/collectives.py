"""Collective helpers: latency-hiding patterns used by the train loop.

XLA's SPMD partitioner already overlaps collectives it inserts; these
helpers cover the patterns we control explicitly:

* ``psum_scatter_then_gather`` — decompose an all-reduce into
  reduce-scatter + all-gather so the optimizer update runs on 1/axis_size
  of each gradient (ZeRO-2 update placement);
* ``delayed_psum`` — start a gradient all-reduce one microbatch early by
  accumulating into a carried buffer (compute/communication overlap in the
  microbatched train loop);
* ``flat_axis_index`` / ``all_concat`` — gather/merge
  primitives for the sharded WindTunnel pipeline (core/sharded_pipeline):
  a tuple of mesh axes treated as one flattened collective axis, with the
  first name most significant — consistent with ``lax.all_gather`` tiled
  concatenation order over the same tuple.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisNames = Union[str, Sequence[str]]


def _as_tuple(axis_names: AxisNames) -> tuple:
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def flat_axis_index(axis_names: AxisNames) -> jnp.ndarray:
    """Row-major linear index over a tuple of mesh axes (first name most
    significant), matching the shard order of a leading array dimension
    partitioned with ``PartitionSpec(tuple(axis_names), ...)``."""
    idx = jnp.int32(0)
    for name in _as_tuple(axis_names):
        idx = idx * lax.psum(jnp.int32(1), name) + lax.axis_index(name)
    return idx


def all_concat(tree, axis_names: AxisNames):
    """All-gather every array leaf along its leading dim (tiled), i.e.
    concatenate the per-shard tables into the replicated global table —
    the merge half of the sharded GraphBuilder's edge dedup."""
    axes = _as_tuple(axis_names)
    return jax.tree.map(
        lambda x: lax.all_gather(x, axes, axis=0, tiled=True), tree)


def psum_scatter_then_gather(x: jnp.ndarray, axis_name: str,
                             scatter_dim: int = 0):
    """all_reduce(x) == all_gather(psum_scatter(x)) — but the caller can run
    its elementwise update between the two halves on 1/N of the data."""
    pieces = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dim,
                              tiled=True)
    return pieces


def gather_after_update(pieces: jnp.ndarray, axis_name: str,
                        gather_dim: int = 0):
    return lax.all_gather(pieces, axis_name, axis=gather_dim, tiled=True)


def microbatch_grads(loss_fn, params, batches, *, accum_dtype=jnp.float32):
    """Gradient accumulation over leading-dim microbatches via lax.scan.
    The per-microbatch psum that SPMD inserts overlaps with the next
    microbatch's forward pass (double buffering by construction)."""
    def one(carry, mb):
        acc = carry
        _, g = jax.value_and_grad(loss_fn)(params, mb)
        acc = jax.tree.map(lambda a, b: a + b.astype(accum_dtype), acc, g)
        return acc, None

    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, accum_dtype), params)
    total, _ = lax.scan(one, zeros, batches)
    n = jax.tree.leaves(batches)[0].shape[0]
    return jax.tree.map(lambda g: g / n, total)
