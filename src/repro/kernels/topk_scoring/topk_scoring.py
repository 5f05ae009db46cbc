"""Fused block scoring + per-block top-k Pallas kernel.

Roofline motivation: brute-force candidate scoring is HBM-bound on the
(Q, N) score matrix. Fusing the top-k selection into the scoring block keeps
scores in VMEM and writes only one 128-lane tile of partials per
(query block, candidate block) back to HBM; the final cross-block merge is
small next to the corpus read. Candidate blocks stream through VMEM sized
by BlockSpec.

Top-k inside the kernel is k rounds of (max, first argmax, mask) on the
VMEM score block — branch-free VPU code, no sort network. The k partials of
a block land in the first k lanes of a ``tile_width(k)``-lane output tile
(the rest stay −inf / −1); the wrappers slice them back out before the
merge. Every block is (8, 128)-aligned or spans the whole array dim, which
is what the TPU compiler requires of a block's last two dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128
# f32 scoring runs at full f32 precision on the MXU (the XLA reference
# backends ask for the same), so kernel and reference rank alike
_F32_DOT = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # contract the last dim of both operands


def tile_width(k: int) -> int:
    """Lane width of one block's partial top-k tile: k rounded up to 128."""
    return -(-k // LANES) * LANES


def extract_topk(scores, ids_of, *, k: int, width: int):
    """k rounds of (max, first argmax, mask) over a (bq, bn) score block.

    ``ids_of(hit, arg)`` maps the one-hot column mask / column index of
    each row's current maximum to the id written out.  Returns
    (scores (bq, width), ids (bq, width)): round i fills lane i, lanes past
    k stay −inf / −1.  Equal scores extract in ascending column order."""
    bq, bn = scores.shape
    col = lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    lane = lax.broadcasted_iota(jnp.int32, (bq, width), 1)

    def body(i, carry):
        scores, out_s, out_i = carry
        m = jnp.max(scores, axis=1, keepdims=True)                 # (bq, 1)
        arg = jnp.min(jnp.where(scores == m, col, bn), axis=1,
                      keepdims=True)                               # (bq, 1)
        hit = col == arg
        out_s = jnp.where(lane == i, m, out_s)
        out_i = jnp.where(lane == i, ids_of(hit, arg), out_i)
        return jnp.where(hit, -jnp.inf, scores), out_s, out_i

    out_s = jnp.full((bq, width), -jnp.inf, jnp.float32)
    out_i = jnp.full((bq, width), -1, jnp.int32)
    _, out_s, out_i = lax.fori_loop(0, k, body, (scores, out_s, out_i))
    return out_s, out_i


def merge_partials(partial_s, partial_i, *, k: int, width: int):
    """Cross-block merge: keep the first k lanes of every block's tile
    (block-major, so ties still favour the earlier block) and take the
    global top-k."""
    qn = partial_s.shape[0]
    s = partial_s.reshape(qn, -1, width)[:, :, :k].reshape(qn, -1)
    i = partial_i.reshape(qn, -1, width)[:, :, :k].reshape(qn, -1)
    top_s, pos = lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=1)


def _topk_kernel(q_ref, c_ref, s_out_ref, i_out_ref, *, k: int, width: int,
                 block_n: int):
    j = pl.program_id(1)                       # candidate-block index
    scores = lax.dot_general(q_ref[...], c_ref[...], _NT,
                             precision=_F32_DOT,
                             preferred_element_type=jnp.float32)  # (bq, bn)
    s_out_ref[...], i_out_ref[...] = extract_topk(
        scores, lambda hit, arg: j * block_n + arg, k=k, width=width)


def _topk_int8_kernel(q_ref, c_ref, s_out_ref, i_out_ref, *, k: int,
                      width: int, block_n: int, n_valid: int):
    """int8 variant: codes dot in int8 with an int32 accumulator (the MXU's
    quantized path on TPU), ranking on the raw integer dot — the global
    query/corpus scales are positive constants, so the int32 order equals
    the dequantized order.  Padding is masked by true row count
    (``n_valid``), the lsh kernel's scheme — an int8 sentinel coordinate
    can't work, the widest code is ±127."""
    j = pl.program_id(1)
    scores = lax.dot_general(q_ref[...], c_ref[...], _NT,
                             preferred_element_type=jnp.int32
                             ).astype(jnp.float32)
    ids = j * block_n + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(ids < n_valid, scores, -jnp.inf)
    s_out_ref[...], i_out_ref[...] = extract_topk(
        scores, lambda hit, arg: j * block_n + arg, k=k, width=width)


def _gathered_kernel(q_ref, c_ref, i_ref, s_out_ref, i_out_ref, *, k: int,
                     width: int):
    """Per-query candidate scoring: each query row scores ITS OWN candidate
    block (the ivfflat probe gather), a batched (1, d) x (bc, d) product per
    query on the MXU; the running top-k is the same extraction as
    _topk_kernel, with ids read from the candidate-id block."""
    q = q_ref[...]                              # (bq, d)
    c = c_ref[...]                              # (bq, bc, d)
    ids = i_ref[...]                            # (bq, bc) int32, -1 invalid
    bq, bc, _ = c.shape
    scores = lax.dot_general(q[:, None, :], c, (((2,), (2,)), ((0,), (0,))),
                             precision=_F32_DOT,
                             preferred_element_type=jnp.float32)
    scores = jnp.where(ids >= 0, scores.reshape(bq, bc), -jnp.inf)
    # id extraction without a dynamic gather: mask-select the argmax col
    out_s, out_i = extract_topk(
        scores, lambda hit, arg: jnp.sum(jnp.where(hit, ids, 0), axis=1,
                                         keepdims=True), k=k, width=width)
    s_out_ref[...] = out_s
    i_out_ref[...] = jnp.where(jnp.isfinite(out_s), out_i, -1)


def _partials_call(kernel, grid, in_specs, args, *, qn: int, nc: int,
                   block_q: int, width: int, interpret: bool):
    """pallas_call with the shared (Q, nc * width) partial-tile outputs."""
    out_spec = pl.BlockSpec((block_q, width), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((qn, nc * width), jnp.float32),
                   jax.ShapeDtypeStruct((qn, nc * width), jnp.int32)],
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_c", "interpret"))
def gathered_topk_pallas(queries: jnp.ndarray, cand_vecs: jnp.ndarray,
                         cand_ids: jnp.ndarray, *, k: int, block_q: int = 8,
                         block_c: int = 256, interpret: bool = False):
    """queries (Q, D) f32, cand_vecs (Q, C, D) f32, cand_ids (Q, C) i32
    (−1 = invalid slot) -> (scores (Q, k), ids (Q, k)).

    Q must be a multiple of block_q and C of block_c (ops.py pads).
    """
    qn, d = queries.shape
    c = cand_vecs.shape[1]
    nq, nc = qn // block_q, c // block_c
    width = tile_width(k)
    partial_s, partial_i = _partials_call(
        functools.partial(_gathered_kernel, k=k, width=width),
        (nq, nc),
        [pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
         pl.BlockSpec((block_q, block_c, d), lambda i, j: (i, j, 0)),
         pl.BlockSpec((block_q, block_c), lambda i, j: (i, j))],
        (queries, cand_vecs, cand_ids), qn=qn, nc=nc, block_q=block_q,
        width=width, interpret=interpret)
    top_s, top_i = merge_partials(partial_s, partial_i, k=k, width=width)
    return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret"))
def topk_scores_pallas(queries: jnp.ndarray, corpus: jnp.ndarray, *, k: int,
                       block_q: int = 128, block_n: int = 1024,
                       interpret: bool = False):
    """queries (Q, D) f32, corpus (N, D) f32 ->
    (scores (Q, k), ids (Q, k)), inner-product metric.

    Q must be a multiple of block_q and N of block_n (ops.py pads).
    """
    qn, d = queries.shape
    n = corpus.shape[0]
    nq, nc = qn // block_q, n // block_n
    width = tile_width(k)
    partial_s, partial_i = _partials_call(
        functools.partial(_topk_kernel, k=k, width=width, block_n=block_n),
        (nq, nc),
        [pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
         pl.BlockSpec((block_n, d), lambda i, j: (j, 0))],
        (queries, corpus), qn=qn, nc=nc, block_q=block_q, width=width,
        interpret=interpret)
    return merge_partials(partial_s, partial_i, k=k, width=width)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret",
                                    "n_valid"))
def topk_scores_int8_pallas(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *,
                            k: int, block_q: int = 128, block_n: int = 1024,
                            interpret: bool = False, n_valid: int = None):
    """q_codes (Q, D) i8, c_codes (N, D) i8 ->
    (int-dot scores as f32 (Q, k), ids (Q, k)).

    Q must be a multiple of block_q and N of block_n (ops.py pads; rows at
    or past ``n_valid`` are masked to −inf/−1 inside the kernel).
    """
    qn, d = q_codes.shape
    n = c_codes.shape[0]
    nq, nc = qn // block_q, n // block_n
    width = tile_width(k)
    partial_s, partial_i = _partials_call(
        functools.partial(_topk_int8_kernel, k=k, width=width,
                          block_n=block_n,
                          n_valid=n if n_valid is None else n_valid),
        (nq, nc),
        [pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
         pl.BlockSpec((block_n, d), lambda i, j: (j, 0))],
        (q_codes, c_codes), qn=qn, nc=nc, block_q=block_q, width=width,
        interpret=interpret)
    return merge_partials(partial_s, partial_i, k=k, width=width)
