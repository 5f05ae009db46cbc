"""Pure-jnp oracle for lsh_hamming.

Kept free of ``repro.retrieval`` imports: the retrieval layer dispatches
*down* into the kernel package through the scoring-backend registry
(retrieval/backends.py), so anything here importing retrieval back up would
be a cycle.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.kernels.lsh_hamming.lsh_hamming import _popcount


def hamming_topk_ref_t(q_codes, c_codes_t, *, k: int):
    """q_codes (Q, W) x corpus codes transposed (W, N), the index layout."""
    ham = _popcount(q_codes[:, :, None] ^ c_codes_t[None]).sum(1)
    top_s, top_i = lax.top_k(-ham.astype(jnp.float32), k)
    return top_s, top_i.astype(jnp.int32)


def hamming_topk_ref(q_codes, c_codes, *, k: int):
    """Row-major corpus codes (N, W)."""
    return hamming_topk_ref_t(q_codes, c_codes.T, k=k)
