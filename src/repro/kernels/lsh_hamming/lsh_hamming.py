"""Packed sign-LSH Hamming top-k Pallas kernel.

Codes are n_bits sign bits packed into int32 lanes (retrieval/lsh.py).
Per (query_block, code_block): XOR + branch-free popcount + sum over words,
then the same fused running top-k (k rounds of max/mask) as topk_scoring —
the (Q, N) Hamming matrix never leaves VMEM. The corpus codes come in
transposed, (W, N), so each block's codes lie along lanes. Bit ops are
pure VPU work; packing gives a 32x density win over scoring float
projections.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.topk_scoring.topk_scoring import (extract_topk,
                                                     merge_partials,
                                                     tile_width)


def _popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Branch-free popcount of int32 lanes (SWAR, logical shifts only —
    no unsigned types or multiplies, so the same code lowers for the TPU
    compiler and for XLA)."""
    x = x.astype(jnp.int32)
    srl = lax.shift_right_logical
    x = x - (srl(x, 1) & 0x55555555)
    x = (x & 0x33333333) + (srl(x, 2) & 0x33333333)
    x = (x + srl(x, 4)) & 0x0F0F0F0F
    x = x + srl(x, 8)
    x = x + srl(x, 16)
    return x & 0x3F


def _hamming_kernel(q_ref, ct_ref, s_out_ref, i_out_ref, *, k: int,
                    width: int, block_n: int, n_words: int, n: int):
    j = pl.program_id(1)
    q = q_ref[...]                              # (bq, W) int32
    ct = ct_ref[...]                            # (W, bn) int32, transposed
    # dist[a, b] = sum_w popcount(q[a, w] ^ c[b, w])
    dist = jnp.zeros((q.shape[0], ct.shape[1]), jnp.int32)
    for w in range(n_words):                    # static unroll over words
        dist = dist + _popcount(q[:, w:w + 1] ^ ct[w:w + 1, :])
    neg = -dist.astype(jnp.float32)             # top-k of -distance
    ids = j * block_n + lax.broadcasted_iota(jnp.int32, neg.shape, 1)
    neg = jnp.where(ids < n, neg, -jnp.inf)     # ragged last block
    s_out_ref[...], i_out_ref[...] = extract_topk(
        neg, lambda hit, arg: j * block_n + arg, k=k, width=width)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret"))
def hamming_topk_pallas(q_codes: jnp.ndarray, c_codes_t: jnp.ndarray, *,
                        k: int, block_q: int = 128, block_n: int = 1024,
                        interpret: bool = False):
    """q_codes (Q, W) i32, c_codes_t (W, N) i32 — the corpus codes
    transposed, so a block's codes sit along lanes ->
    (neg_hamming (Q, k) f32, ids (Q, k) i32).  Q must be a multiple of
    ``block_q``; N need not be one of ``block_n``: the last block runs past
    N and its extra columns score -inf."""
    qn, w = q_codes.shape
    n = c_codes_t.shape[1]
    nq, nc = qn // block_q, pl.cdiv(n, block_n)
    width = tile_width(k)
    out_spec = pl.BlockSpec((block_q, width), lambda i, j: (i, j))
    partial_s, partial_i = pl.pallas_call(
        functools.partial(_hamming_kernel, k=k, width=width,
                          block_n=block_n, n_words=w, n=n),
        grid=(nq, nc),
        in_specs=[
            pl.BlockSpec((block_q, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((qn, nc * width), jnp.float32),
            jax.ShapeDtypeStruct((qn, nc * width), jnp.int32),
        ],
        interpret=interpret,
    )(q_codes, c_codes_t)
    return merge_partials(partial_s, partial_i, k=k, width=width)
