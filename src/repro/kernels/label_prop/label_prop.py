"""Weighted label-propagation round as a Pallas kernel (GraphSampler hot
loop, Alg. 2 steps 1-3).

Layout: degree-capped ELL adjacency. The neighbour-label gather happens
OUTSIDE the kernel (XLA gather, HBM-bound); the kernel fuses the O(K^2)
per-node same-label weight reduction + argmax + min-label tie-break that
dominates compute. The sort-based reference implementation pays an
O(E log E) bitonic sort per round; the ELL kernel is O(N*K^2) dense VPU/MXU
work with zero shuffles — the §Perf hillclimb for the paper-technique cell
measures exactly this trade.

The kernel works slot-major: neighbour labels and weights arrive as
(K, N) arrays with the nodes along lanes, so a node block is a lane-dense
(K, bn) tile and the new labels leave as a lane-dense (1, N) row. A
node-major (N, K) array with K=32 would occupy four times its size in HBM
(the TPU tiles the last dim by 128), and an (N, 1) label column 128 times.
For every slot k, the slots holding the same label collect w[k] (a
broadcast compare + select, summed in ascending k —
:func:`same_label_scores`, which the XLA engines share so both round
identically); ties are broken toward the smaller label with an exact
two-pass (max score, min label among maxima).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I32_MAX = jnp.iinfo(jnp.int32).max


def same_label_scores(lab: jnp.ndarray, wm: jnp.ndarray) -> jnp.ndarray:
    """scores[j, n] = sum_k wm[k, n] * [lab[k, n] == lab[j, n]] over
    slot-major (K, N) blocks, accumulated in ascending k: one fixed order
    of f32 adds, so the XLA engines and the Pallas kernel produce
    bit-identical scores."""
    scores = jnp.zeros(wm.shape, jnp.float32)
    for k in range(lab.shape[0]):               # static unroll over slots
        scores = scores + jnp.where(lab == lab[k:k + 1], wm[k:k + 1], 0.0)
    return scores


def best_labels(lab: jnp.ndarray, wgt: jnp.ndarray) -> jnp.ndarray:
    """Slot-major neighbour labels (K, N) (−1 padding) and weights ->
    (1, N): the highest-scoring label, ties to the smaller one, and
    ``I32_MAX`` for a node without neighbours."""
    mask = lab >= 0
    scores = jnp.where(mask, same_label_scores(lab, jnp.where(mask, wgt, 0.0)),
                       -jnp.inf)
    smax = jnp.max(scores, axis=0, keepdims=True)
    return jnp.min(jnp.where((scores == smax) & mask, lab, I32_MAX), axis=0,
                   keepdims=True)


def _lp_kernel(lab_ref, w_ref, out_ref):
    out_ref[...] = best_labels(lab_ref[...], w_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def label_prop_round_pallas(nbr_labels: jnp.ndarray, wgt: jnp.ndarray, *,
                            block_n: int = 256, interpret: bool = False):
    """nbr_labels (K, N) i32 (pre-gathered neighbour labels, -1 pad),
    wgt (K, N) f32 -> best labels (N,) i32, ``I32_MAX`` where a node has
    no neighbour.  A last block that runs past N reads padding whose
    results are never written: every node (lane) is independent."""
    k, n = nbr_labels.shape
    spec = pl.BlockSpec((k, block_n), lambda i: (0, i))
    return pl.pallas_call(
        _lp_kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(nbr_labels, wgt)[0]
