"""Dispatch wrappers: gather neighbour labels (XLA), run the Pallas round
kernel on the slot-major (K, N) layout (interpret mode off-TPU) and keep
the labels of nodes without neighbours.  The node block resolves through
the autotuner table (kernels/tuning.py): explicit kwarg > tuned entry for
the row-count bucket > hard-coded default, resolved in the plain-python
wrappers before any jitted call."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.label_prop.label_prop import label_prop_round_pallas
from repro.kernels.label_prop.ref import keep_isolated, round_slot_major


def pallas_round(nbr_labels_t: jnp.ndarray, wgt_t: jnp.ndarray,
                 own: jnp.ndarray, *, block_n: int = None):
    """Run the Pallas round kernel on pre-gathered slot-major neighbour
    labels (K, N); one block spans N when N fits in it.  Shared by the
    single-device pallas engine and the sharded pipeline's local node
    blocks."""
    rows = nbr_labels_t.shape[1]
    block_n = tuning.resolve("label_prop_round", n=rows, dtype="float32",
                             block_n=block_n)["block_n"]
    best = label_prop_round_pallas(nbr_labels_t, wgt_t,
                                   block_n=min(block_n, rows),
                                   interpret=tuning.interpret_mode())
    return keep_isolated(best, own)


def label_prop_round_t(labels: jnp.ndarray, nbr_t: jnp.ndarray,
                       wgt_t: jnp.ndarray, *, block_n: int = None,
                       use_kernel: bool = True):
    """One LP round over slot-major ELL adjacency: labels (N,), nbr_t
    (K, N) node ids (-1 pad), wgt_t (K, N). Returns new labels (N,)."""
    block_n = tuning.resolve("label_prop_round", n=labels.shape[0],
                             dtype="float32", block_n=block_n)["block_n"]
    return _label_prop_round(labels, nbr_t, wgt_t, block_n=block_n,
                             use_kernel=use_kernel)


def label_prop_round(labels: jnp.ndarray, nbr: jnp.ndarray,
                     wgt: jnp.ndarray, *, block_n: int = None,
                     use_kernel: bool = True):
    """One LP round over node-major ELL adjacency: labels (N,), nbr (N, K)
    node ids (-1 pad), wgt (N, K), transposed to :func:`label_prop_round_t`
    on every call. Returns new labels (N,)."""
    return label_prop_round_t(labels, nbr.T, wgt.T, block_n=block_n,
                              use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("block_n", "use_kernel"))
def _label_prop_round(labels: jnp.ndarray, nbr_t: jnp.ndarray,
                      wgt_t: jnp.ndarray, *, block_n: int, use_kernel: bool):
    lab = jnp.where(nbr_t >= 0, labels[jnp.maximum(nbr_t, 0)], -1)
    if not use_kernel:
        return round_slot_major(lab, wgt_t, labels)
    return pallas_round(lab, wgt_t, labels, block_n=block_n)
