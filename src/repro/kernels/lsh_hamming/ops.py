"""Dispatch wrapper for lsh_hamming: block choice, query padding,
interpret mode off-TPU.

The corpus codes come in the index's layout, transposed (W, N)
(retrieval/lsh.py builds them so once), and go to the kernel as they are:
no per-call copy of the corpus.  N need not be a multiple of the block;
the kernel masks columns past N and their ids come back as −1 / −inf.
``k`` is clamped to the corpus size and the result padded back, so
engine-path shapes never crash ``lax.top_k``.

Block sizes resolve through the autotuner table (kernels/tuning.py):
explicit kwarg > tuned entry for the corpus-size bucket > hard-coded
default, resolved in the plain-python outer wrapper before the inner jit
(a lookup inside a jitted body would go stale when the table changes).
A corpus no wider than the block is one whole-width block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.lsh_hamming.lsh_hamming import hamming_topk_pallas
from repro.kernels.lsh_hamming.ref import hamming_topk_ref_t
from repro.kernels.topk_scoring.ref import pad_topk as _pad_topk


def hamming_topk_t(q_codes: jnp.ndarray, c_codes_t: jnp.ndarray, *, k: int,
                   block_q: int = None, block_n: int = None,
                   use_kernel: bool = True):
    """q_codes (Q, W) x corpus codes transposed (W, N) ->
    (−distance f32[Q, k], ids i32[Q, k])."""
    blocks = tuning.resolve("hamming_topk", n=c_codes_t.shape[1],
                            dtype=c_codes_t.dtype, block_q=block_q,
                            block_n=block_n)
    return _hamming_topk_t(q_codes, c_codes_t, k=k, use_kernel=use_kernel,
                           **blocks)


def hamming_topk(q_codes: jnp.ndarray, c_codes: jnp.ndarray, *, k: int,
                 **kw):
    """Row-major corpus codes (N, W): transposes them per call."""
    return hamming_topk_t(q_codes, c_codes.T, k=k, **kw)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "use_kernel"))
def _hamming_topk_t(q_codes: jnp.ndarray, c_codes_t: jnp.ndarray, *, k: int,
                    block_q: int, block_n: int, use_kernel: bool):
    n = c_codes_t.shape[1]
    k_eff = min(k, n)
    if not use_kernel:
        return _pad_topk(*hamming_topk_ref_t(q_codes, c_codes_t, k=k_eff), k)
    qn = q_codes.shape[0]
    bq = min(block_q, max(8, qn))
    bn = min(block_n, n)
    qp = jnp.pad(q_codes, ((0, (-qn) % bq), (0, 0)))
    s, i = hamming_topk_pallas(qp, c_codes_t, k=k_eff, block_q=bq,
                               block_n=bn, interpret=tuning.interpret_mode())
    if n % bn:
        bad = i >= n
        s = jnp.where(bad, -jnp.inf, s)
        i = jnp.where(bad, -1, i)
    return _pad_topk(s[:qn], i[:qn], k)
