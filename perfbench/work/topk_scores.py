"""Work of one exact top-k scoring call (``kernels/topk_scoring``):
queries (Q, D) against a corpus (N, D), both float32.

The algorithm must form every inner product (2·Q·N·D operations) and read
the corpus and the queries once ((N·D + Q·D)·4 bytes).  The sentinel pad
and the merge of partial top-k lists are the implementation's, not the
algorithm's, so they count against the kernel's time and not its work.
"""
from __future__ import annotations

from typing import Tuple

DTYPE = "f32"


def work(q: int, n: int, d: int, elem_bytes: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of scoring ``q`` queries against ``n`` rows."""
    return 2.0 * q * n * d, float((n * d + q * d) * elem_bytes)
