"""Sort/segment primitives shared by the WindTunnel core.

All WindTunnel MapReduce stages (Alg. 1 & 2 of the paper) are expressed as
sort-by-key + reduce-over-runs. On TPU, ``jax.lax.sort`` lowers to a bitonic
sort network and ``segment_*`` to scatter-adds, which is the idiomatic XLA
replacement for a MapReduce shuffle (see DESIGN.md §2).

Static-shape convention: every "table" is a fixed-length array bundle with a
``valid`` mask. Masked rows carry sentinel keys that sort to the end and are
dropped on scatter (``mode='drop'``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

I32_MAX = jnp.iinfo(jnp.int32).max


def sort_by(keys: tuple, payloads: tuple = ()):
    """Lexicographic ascending sort by ``keys``, carrying ``payloads``.

    Returns (sorted_keys, sorted_payloads).
    """
    operands = tuple(keys) + tuple(payloads)
    out = lax.sort(operands, num_keys=len(keys), is_stable=True)
    return out[: len(keys)], out[len(keys):]


def run_starts(*keys) -> jnp.ndarray:
    """Boolean mask marking the first element of each run of equal keys.

    ``keys`` must already be sorted (lexicographically).
    """
    n = keys[0].shape[0]
    changed = jnp.zeros((n - 1,), dtype=bool)
    for k in keys:
        changed = changed | (k[1:] != k[:-1])
    return jnp.concatenate([jnp.ones((1,), dtype=bool), changed])


def run_segment_ids(starts: jnp.ndarray) -> jnp.ndarray:
    """Map each position to the index of the run it belongs to."""
    return jnp.cumsum(starts.astype(jnp.int32)) - 1


def group_rank(starts: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its run (0-based). ``starts`` from run_starts."""
    n = starts.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # cummax, not associative_scan: on TPU it lowers to one reduce_window,
    # where a 16M-long associative_scan takes the compiler minutes
    group_start = lax.cummax(jnp.where(starts, iota, 0))
    return iota - group_start


def masked_min(values: jnp.ndarray, mask: jnp.ndarray, axis=None):
    big = jnp.asarray(jnp.inf if jnp.issubdtype(values.dtype, jnp.floating) else I32_MAX,
                      dtype=values.dtype)
    return jnp.min(jnp.where(mask, values, big), axis=axis)


def segment_sum(data, segment_ids, num_segments):
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_max(data, segment_ids, num_segments):
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_min(data, segment_ids, num_segments):
    return jax.ops.segment_min(data, segment_ids, num_segments=num_segments)


def reduce_by_key_sum(keys: tuple, values: jnp.ndarray, valid: jnp.ndarray):
    """Sum ``values`` over equal-``keys`` groups.

    Returns per-position arrays aligned with the *sorted* order:
      sorted_keys, run_start mask, per-run sum broadcast back to positions,
      segment ids. Masked rows get sentinel keys and zero value.
    """
    skeys = tuple(jnp.where(valid, k, I32_MAX) for k in keys)
    svals = jnp.where(valid, values, jnp.zeros((), values.dtype))
    (sk, sv) = sort_by(skeys, (svals, valid.astype(jnp.int32)))
    sorted_vals, sorted_valid = sv
    starts = run_starts(*sk)
    seg = run_segment_ids(starts)
    sums = segment_sum(sorted_vals, seg, num_segments=values.shape[0])
    return sk, starts, sums[seg], seg, sorted_valid.astype(bool)
