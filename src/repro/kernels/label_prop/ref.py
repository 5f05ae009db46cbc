"""Pure-jnp oracle for the label_prop kernel — must agree exactly with
core.label_prop.ell_round (same semantics, same tie-break, same order of
f32 adds: both score through ``same_label_scores``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.label_prop.label_prop import I32_MAX, best_labels


def keep_isolated(best: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """New labels from a round's best labels: nodes without neighbours
    (``I32_MAX``) keep their current label."""
    return jnp.where(best == I32_MAX, labels, best).astype(jnp.int32)


def round_slot_major(nbr_labels_t, wgt_t, labels):
    """One round in XLA over slot-major (K, N) neighbour labels/weights."""
    return keep_isolated(best_labels(nbr_labels_t, wgt_t)[0], labels)


def label_prop_round_ref(nbr_labels, wgt, labels):
    """One round over node-major (N, K) neighbour labels/weights."""
    return round_slot_major(nbr_labels.T, wgt.T, labels)
